"""Device milliseconds per traced fit on the busiest chip: of the operations
whose names match ``include``, or of everything but those matching
``exclude``."""


def read(run, include=None, exclude=None):
    tr = run["trace"]
    if not run["traced_fits"] or not tr.chips:
        return None
    chip = tr.fullest_chip()
    if include is not None:
        s = tr.matching_s(chip, include)
        if s == 0.0:
            return None
    else:
        s = tr.busy_s(chip) - tr.matching_s(chip, exclude or [])
    return 1e3 * s / len(run["traced_fits"])
