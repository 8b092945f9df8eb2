"""90th percentile of the same ages; only where the window holds ten
products or more beyond it."""

import statistics


def read(run):
    ages = run.exit_ages()
    if len(ages) < 100:
        return None
    return statistics.quantiles(ages, n=10)[-1]
