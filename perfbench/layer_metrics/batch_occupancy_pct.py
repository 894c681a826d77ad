"""Serving engine: slots decoding over slots there are, the mean over
the window's engine steps (sampled after each step)."""

LAYER = "serving engine"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(run):
    window = run.get("window", {})
    steps = window.get("steps")
    if not steps:
        return None
    return 100.0 * sum(s[2] for s in steps) / (len(steps) * window["n_slots"])
