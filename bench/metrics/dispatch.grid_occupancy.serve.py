"""Share of the rows the expert kernels' grid walks that active blocks
cover (%): Σ`sched/padded_rows` over Σ`sched/grid_rows`, summed over the
traced steps.  None where the program does not count its grid rows."""


def read(run):
    steps = run.get("steps")
    if not steps or any("sched/grid_rows" not in s for s in steps):
        return None
    grid = sum(s["sched/grid_rows"] for s in steps)
    padded = sum(s["sched/padded_rows"] for s in steps)
    return 100.0 * padded / grid if grid else None
