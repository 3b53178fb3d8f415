"""95th percentile of every gap between consecutive output tokens of a
request whose later token came in the window; each token is stamped when
the step that delivered it returns (``step()`` ends in the blocking host
read of the sampled ids)."""

from bench.readings import percentile, token_gaps_ms


def read(run):
    return percentile(token_gaps_ms(run), 95)
