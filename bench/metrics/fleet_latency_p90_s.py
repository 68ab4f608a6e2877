"""The 90th percentile, over every request answered inside the window, of
the seconds from its submission to its answer."""

import statistics


def read(run):
    lat = [r.done - r.submit for r in run.window_requests()]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=10, method="inclusive")[8]
