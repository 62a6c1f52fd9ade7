"""Expert layer: how unevenly the window's tokens fell on the experts —
the largest expert's share of Δengine_expert_tokens_total over the mean
expert's, experts and layers taken together (the counter sums a step's
expert layers). 1.0 is an even load; the seeded random router of a
benchmark checkpoint reads close to it. A program without the counter
has nothing to read."""

TOKENS = "engine_expert_tokens_total"


def reduce(trace, run):
    before, after = run.get("metrics_before"), run.get("metrics_after")
    if before is None or after is None or TOKENS not in after:
        return None
    was = {labels.get("expert"): v for labels, v in before.get(TOKENS, [])}
    got = [v - was.get(labels.get("expert"), 0.0)
           for labels, v in after[TOKENS]]
    # an expert no token reached has no sample yet: it counts as 0
    n = int(run["config"].get("num_experts") or len(got))
    got += [0.0] * max(0, n - len(got))
    total = sum(got)
    if total <= 0:
        return None
    return max(got) / (total / len(got))
