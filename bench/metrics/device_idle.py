"""Share of the traced window in which no device ran an operation [%]."""


def read(summary, facts):
    share = summary.idle_share
    return None if share is None else 100.0 * share
