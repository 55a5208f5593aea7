"""Ratio of /metrics deltas over the window: the summed movement of the
`numerator` series over that of the `denominator` series, times `scale`.
Nothing where the denominator did not move. args: numerator, denominator,
scale."""


def moved(run, names: list) -> float:
    def total(metrics, name):
        return sum(value for series, value in metrics.items()
                   if series == name or series.startswith(name + "{"))
    return sum(total(run.metrics_after, name) - total(run.metrics_before, name)
               for name in names)


def read(run, numerator: list, denominator: list, scale: float = 1.0):
    if run.metrics_before is None or run.metrics_after is None:
        return None
    below = moved(run, denominator)
    return float(scale * moved(run, numerator) / below) if below else None
