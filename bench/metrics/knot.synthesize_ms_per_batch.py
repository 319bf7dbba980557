"""The screen's ``knot.synthesize`` phase, the synthesis of the kept chains
(``synthesize_batch``), in ms per batch, over the campaigns that finished
in the window (``campaign_report`` ``phases``)."""
from phases import screen_phase_ms


def read(r):
    return screen_phase_ms(r, "knot.synthesize")
