"""Mean host time per search batch of the sealed-row stages (coarse DTW
probing, query tables, fine ADC ranking), from the program's fenced
``obs`` spans."""


def read(run):
    n = len(run.samples("stage_seconds", stage="index.search.coarse"))
    if not n:
        return None
    tot = sum(
        sum(run.samples("stage_seconds", stage=f"index.search.{s}"))
        for s in ("coarse", "lut", "fine")
    )
    return tot / n * 1e3
