"""sampler_s: seconds per query in the data layer, on the harness's clock:
building the PreMapSampler (its permutation of the store) and every take."""


def read(run):
    qs = run.records.get("queries")
    if not qs:
        return None
    return sum(q["sampler_s"] for q in qs) / len(qs)
