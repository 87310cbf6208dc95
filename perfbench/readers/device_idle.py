"""Share of the traced window in which no operation ran on the busiest chip."""


def read(run):
    tr = run["trace"]
    if not tr.chips or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s(tr.fullest_chip()) / tr.window_s)
