"""Experts: of the experts a token is routed to in a layer, how many
are held on this chip. Over the window's `engine.step` spans, the
program's `moe_held_pairs` (the (token, expert) pairs that landed on
the experts held here) over the tokens routed (`moe_routed_pairs`,
the pairs the router dealt over all the published experts, over the
experts a token takes). With 16 of 256 experts held and 8 a token an
even router gives 8 x 16 / 256 = 0.5; the grouped kernels' rows, and
a step's time in them, follow it. None on a program without the
counts."""

import program_trace

LAYER = "experts"
UNIT = "pairs/token"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    steps, trace = program_trace.records(run, "engine.step")
    if steps is None:
        return None
    held = routed = 0
    for s in steps:
        counts = s[trace.COUNTS]
        if counts.get("moe_routed_pairs"):
            held += counts["moe_held_pairs"]
            routed += counts["moe_routed_pairs"]
    if not routed:
        return None
    top_k = run["cell"]["model"]["num_experts_per_tok"]
    return held / (routed / top_k)
