"""Run time of the screen tasks, per batch, over the campaigns that
finished in the window (``campaign_report``)."""


def read(r):
    stages = [rep["stages"]["screen"] for rep in r.get("campaign_reports")
              or []]
    tasks = sum(s["tasks"] for s in stages)
    return 1000.0 * sum(s["run_s"] for s in stages) / tasks if tasks else None
