"""Run time of the localize tasks per batch (a skipped localize counts as
a batch with none), over the campaigns that finished in the window."""


def read(r):
    reps = r.get("campaign_reports") or []
    batches = sum(rep["stages"]["screen"]["tasks"] for rep in reps)
    if not batches:
        return None
    return 1000.0 * sum(rep["stages"]["localize"]["run_s"]
                        for rep in reps) / batches
