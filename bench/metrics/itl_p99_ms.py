"""99th percentile of the token gaps of ``itl_p95_ms``.  In a chat cell
about one step in twenty is a mixed (prefill) step, so the 95th
percentile sits on the edge between decode steps and the stalls a prompt
chunk imposes; the 99th lies inside the stalls, which is what a chat user
sees."""

from bench.readings import percentile, token_gaps_ms


def read(run):
    return percentile(token_gaps_ms(run), 99)
