"""Tokens (batch x sequence) of all steps that completed in the window, over
the time from the window's start to the last step's end; all chips
together."""


def read(ctx):
    rec = ctx["rec"]
    ends = rec["step_ends"]
    return rec["tokens_per_step"] * len(ends) / (ends[-1] - rec["t0"])
