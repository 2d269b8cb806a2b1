"""Trainer loop: programs compiled inside the measured window, or loaded
there from the persistent compilation cache (JAX times both as its backend
compile event).  Every shape the window uses is warmed up in set-up, so
anything above 0 is a compile the window paid for."""


def read(run):
    return float(run.compiles_in_window)
