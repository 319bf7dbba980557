"""The screen's ``knot.quality`` phase, the quality draw (``quality_score``),
the cut and the kept ids, in ms per batch, over the campaigns that
finished in the window (``campaign_report`` ``phases``)."""
from phases import screen_phase_ms


def read(r):
    return screen_phase_ms(r, "knot.quality")
