"""pad_share.batch: % of the edge slots dispatched in the window that
were padding, as the service counts it (`ServiceStats.padding_overhead`,
batch rows and shape tails together)."""


def read(run):
    share = run.counters.get("padding_overhead")
    return None if share is None else 100.0 * share
