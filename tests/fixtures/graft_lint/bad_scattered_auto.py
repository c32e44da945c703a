"""Fixture: a string-literal "auto" dispatch branch resolved by a local
heuristic, with no route through a raft_tpu.plan rule."""


def search(index, queries, k, mode="auto"):
    nq = queries.shape[0]
    if mode == "auto":  # LINT-HERE
        mode = "fused" if nq >= 128 else "scan"
    return index.run(queries, k, mode)
