"""Rows the mesh recomputes per row that moved: the shards times each
shard's dirty-row budget (the length of the padded index vector the
engine counted while tracing) over the movers of a TTI."""


def read(run):
    w = run.work
    if "row_budget" not in w:
        return None
    return w["shards"] * w["row_budget"] / w["rows"]
