"""The screen's ``knot.writhe`` phase, the writhe map and its totals, through
the host's wait for them, in ms per batch, over the campaigns that
finished in the window (``campaign_report`` ``phases``)."""
from phases import screen_phase_ms


def read(r):
    return screen_phase_ms(r, "knot.writhe")
