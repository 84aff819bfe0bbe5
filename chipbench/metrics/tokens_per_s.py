"""Output tokens completed per second of the whole serve.

Every output token of every finished request, over the wall time from
the first arrival to the last completion.
"""


def read(run):
    done = [r for r in run["results"] if r["status"] == "ok"]
    if not done:
        return None
    span = (max(r["finish_s"] for r in done)
            - min(r["arrival_s"] for r in run["results"]))
    return sum(r["n_tokens"] for r in done) / span
