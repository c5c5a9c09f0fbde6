"""rounds: EarlSession rounds per query in the window
(EarlyResult.iterations)."""


def read(run):
    qs = run.records.get("queries")
    if not qs:
        return None
    return sum(q["rounds"] for q in qs) / len(qs)
