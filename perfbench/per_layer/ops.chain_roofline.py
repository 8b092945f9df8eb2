"""Least time the chip could take for a gulp's work, counted from the
configuration's shapes, over the time its operations took."""


def read(run):
    t = run.trace()
    if t is None:
        return None
    least, bound = run.least_seconds_per_gulp()
    run.note('ops.chain_roofline sits against the %s peak: least '
             '%.6g ms a gulp' % (bound, least * 1e3))
    return 100.0 * least / (t['busy_s'] / run.gulps())
