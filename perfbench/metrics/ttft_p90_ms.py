"""90th percentile, over all requests DUE in the window, of first token
minus the time the request was due; a shed or failed request counts
with the whole wait.  A tail over some 37 requests a window: it spread
by 9 % in two sets of six runs (my chip runs, PR 34; 16 % between two
runs of one seed in PR 25), so it stands beside ``tpot_p95_ms`` and is
held to no bound."""


def read(ctx):
    return ctx["counters"].get("ttft_p90_ms")
