"""Share of the batched loop's member-iterations spent on members that
had already converged or were padding: the loop runs every member of its
bucket for as many iterations as the slowest needs."""


def read(run):
    bucket = run.info.get("bucket")
    if not run.records or not bucket:
        return None
    paid = sum(bucket * r["max_iterations"] for r in run.records)
    useful = sum(sum(r["iterations"]) for r in run.records)
    return 100.0 * (paid - useful) / paid
