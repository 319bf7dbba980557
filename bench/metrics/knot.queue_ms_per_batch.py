"""Broker queue time (grant spans) of every task, per batch, over the
campaigns that finished in the window (``campaign_report``)."""


def read(r):
    reps = r.get("campaign_reports") or []
    batches = sum(rep["stages"]["screen"]["tasks"] for rep in reps)
    if not batches:
        return None
    queue = sum(s["queue_s"] for rep in reps for s in rep["stages"].values())
    return 1000.0 * queue / batches
