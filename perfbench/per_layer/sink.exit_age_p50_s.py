"""Median age of a product at the sink: the bench's stamp when the
last gulp that contributes to it was written into the source ring, to
its stamp when the product arrived, over all products of the window."""

import statistics


def read(run):
    ages = run.exit_ages()
    return statistics.median(ages) if ages else None
