"""Operations a training step requires, from the configuration and from
what the step consumed.  Nothing is counted for padding, recomputation or
samples that were generated and then dropped, so no change to how the
program executes the step can raise these numbers.

The configuration's family gives the two numbers per token (its ``dims``):
``n``, the matmul parameters a token passes through, and ``attn``, the
forward FLOPs per attended key over all layers.

* A token at grid position i (0-based) attends to i + 1 keys, which costs
  attn * (i + 1) forward.
* Rollout: 2 n plus the attention term for every prompt token (each prompt
  is prefilled once for its group) and every response token of the P * G
  kept samples.
* Learner: 6 n plus three times the attention term for every token of each
  kept hull: the prompt and the response up to its last kept token.
"""
from __future__ import annotations

import numpy as np


def _span_flops(z: dict, start, stop, per_token: float, attn_mult: float):
    """Sum over tokens at positions [start, stop) of per_token plus
    attn_mult times the attention term."""
    start = np.asarray(start, np.float64)
    stop = np.asarray(stop, np.float64)
    n = np.maximum(stop - start, 0)
    # sum of (i + 1) for i in [start, stop)
    ctx = (stop * (stop + 1) - start * (start + 1)) / 2
    attn = z["attn"] * ctx
    return float(np.sum(per_token * n + attn_mult * attn))


def hull_lengths(keep_mask) -> np.ndarray:
    """One past the last kept grid position of each row (0 if none)."""
    keep = np.asarray(keep_mask, bool)
    t = keep.shape[1]
    last = t - np.argmax(keep[:, ::-1], axis=1)
    return np.where(keep.any(axis=1), last, 0)


def rollout_flops(z: dict, prompt_lens, response_lens, group: int) -> float:
    """z: the family's dims; prompt_lens / response_lens of the P * G kept
    rows, groups contiguous."""
    n = z["n"]
    pl = np.asarray(prompt_lens)
    prompts = _span_flops(z, 0, pl[::group], 2.0 * n, 1.0)
    responses = _span_flops(z, pl, pl + np.asarray(response_lens), 2.0 * n,
                            1.0)
    return prompts + responses


def learner_flops(z: dict, hulls) -> float:
    return _span_flops(z, 0, np.asarray(hulls), 6.0 * z["n"], 3.0)
