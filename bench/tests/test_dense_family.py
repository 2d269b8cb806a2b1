"""The dense family module (bench/models/dense_gqa.py) computes what the
benchmark computed before its equations moved there: the same weights from
the seed, and the same reference over the same steps, to the last bit on
the CPU.

The constants were recorded with the bench/ code of commit 179ded4, the
last whose weights.py and reference.py held the dense equations: at the
small sizes and the seed below, ``_readings`` of ``W.leaf_norms(W.make(
config, SEED))`` (the weights, made from the configuration in that code)
and of ``H.follow_reference`` over ``batches``.  The steps are made here
from the seed, so the comparison does not depend on the program's
rollout."""
import jax
import numpy as np

from bench import harness as H
from bench import weights as W

CELL = "qwen3_8b.cot_rpc"
SEED = 2**31 + 12345
SMALL = dict(hidden_size=1024, intermediate_size=3072, num_attention_heads=8,
             num_key_value_heads=2, head_dim=128, num_hidden_layers=2,
             vocab_size=2048)
ROWS, PROMPT, RESPONSE = 16, 24, 48


def small_spec():
    spec = H.load_spec(CELL)
    spec["config"].update(SMALL)
    return spec


def batches(spec, seed, n_steps=3):
    """Steps of ``ROWS`` rows drawn from ``seed``: random tokens, prompt and
    response lengths, and each row's kept prefix cut where the mix's RPC
    design allows (L uniform on {min(C, T) .. T})."""
    rng = np.random.default_rng(seed)
    v = spec["config"]["vocab_size"]
    min_cut = spec["traffic"]["trainer"]["selector_kwargs"]["min_cut"]
    steps = []
    for _ in range(n_steps):
        tokens = rng.integers(0, v, (ROWS, PROMPT + RESPONSE), np.int32)
        prompt_lens = rng.integers(4, PROMPT + 1, ROWS)
        response_lens = rng.integers(4, RESPONSE + 1, ROWS)
        keep = np.zeros(tokens.shape, bool)
        for i, (pl, n) in enumerate(zip(prompt_lens, response_lens)):
            keep[i, pl:pl + rng.integers(min(min_cut, n), n + 1)] = True
        steps.append({"tokens": tokens, "prompt_lens": prompt_lens,
                      "response_lens": response_lens, "keep_mask": keep})
    return steps


def _readings(norms, ref, steps):
    resp = H.response_mask(steps[0])
    logp1 = ref["logp1"][resp]
    return {"leaves": {k: (norms[k], ref["grad1"][k], ref["update"][k])
                       for k in sorted(norms)},
            "loss": list(ref["loss"]),
            "logp1_sum": float(np.sum(logp1, dtype=np.float64)),
            "logp1_max": float(np.max(logp1))}


# name: (norm of the made weight, first gradient's norm, change's norm)
LEAVES = {
    "embed": (1448.8609619140625, 0.020082436501979828,
              3.133145219180733e-05),
    "final_norm": (0.0, 0.014640506356954575,
                   6.27015542704612e-05),
    "head": (45.28714370727539, 0.46521344780921936,
             0.0003576565650291741),
    "l0.ln1": (0.0, 0.01043284498155117,
               6.403820589184761e-05),
    "l0.ln2": (0.0, 0.010971753858029842,
               6.152980495244265e-05),
    "l0.w_down": (31.986818313598633, 0.4407128691673279,
                  0.0005939557449892163),
    "l0.w_gate": (55.37797164916992, 0.26539504528045654,
                  0.0004490861902013421),
    "l0.w_up": (55.44997787475586, 0.25837719440460205,
                0.0004512059676926583),
    "l0.wk": (16.004398345947266, 0.1750490963459015,
              0.00013065757229924202),
    "l0.wo": (31.985000610351562, 0.2360510379076004,
              0.00026034642360173166),
    "l0.wq": (32.016883850097656, 0.17556264996528625,
              0.000261963956290856),
    "l0.wv": (16.005407333374023, 0.23493751883506775,
              0.00013183026749175042),
    "l1.ln1": (0.0, 0.007419086527079344,
               6.315993960015476e-05),
    "l1.ln2": (0.0, 0.008738680742681026,
               6.239563663257286e-05),
    "l1.w_down": (32.01211166381836, 0.3351424038410187,
                  0.000591704563703388),
    "l1.w_gate": (55.36124801635742, 0.2020919770002365,
                  0.0004489253624342382),
    "l1.w_up": (55.45643997192383, 0.19663943350315094,
                0.0004508791316766292),
    "l1.wk": (16.04375648498535, 0.11466170847415924,
              0.00012915748811792582),
    "l1.wo": (31.975961685180664, 0.17316700518131256,
              0.0002618115395307541),
    "l1.wq": (32.021114349365234, 0.11400704830884933,
              0.00026185702881775796),
    "l1.wv": (15.981289863586426, 0.17560385167598724,
              0.00012798314855899662),
}
LOSS = [0.09064415097236633, -0.28913358598947525, -0.13020311668515205]
LOGP1_SUM = -3978.5313816070557
LOGP1_MAX = -5.198334693908691


def test_dense_family_is_the_parent_arithmetic():
    spec = small_spec()
    fam = spec["family"]
    flat0 = jax.device_get(W.make(fam.layout(spec["config"]), SEED))
    steps = batches(spec, SEED)
    got = _readings(W.leaf_norms(flat0, fam.STACKED),
                    H.follow_reference(spec, SEED, steps, flat0), steps)
    assert got["leaves"] == LEAVES
    assert got["loss"] == LOSS
    assert got["logp1_sum"] == LOGP1_SUM
    assert got["logp1_max"] == LOGP1_MAX
