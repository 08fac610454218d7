"""Accepted draft tokens per live verify slot per round over the window:
the change of the engine's ``spec_tokens_accepted_total`` over the change
of ``spec_verify_rounds_total`` (program counters)."""


def read(ctx):
    c0, c1 = ctx["counters"]
    rounds = c1.get("spec_verify_rounds_total", 0) - \
        c0.get("spec_verify_rounds_total", 0)
    if rounds <= 0:
        return None
    return (c1.get("spec_tokens_accepted_total", 0)
            - c0.get("spec_tokens_accepted_total", 0)) / rounds
