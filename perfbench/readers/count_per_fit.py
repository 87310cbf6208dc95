"""A program counter, averaged over the window's fits."""


def read(run, count: str):
    values = [f[count] for f in run["fits"] if count in f]
    return sum(values) / len(values) if values else None
