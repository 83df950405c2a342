"""Least work of one HNSW search request on the device graph, from the
stage's shapes and the configuration's stated walk alone.

The stage is the whole request on the device: the greedy beam walk over the
level-0 adjacency and the exact rerank of its candidate set, whatever
kernels implement them. What the algorithm needs: every row the walk visits
is scored once, so its stored row is read once, and so is its adjacency row
(2 x nlinks neighbour slots of 4 bytes) if it is expanded; the count is
taken for every visited row, which can only make the least time longer and
the share larger than the walk's own. Then the ef candidates are read once
more for the rerank. FLOPs: one multiply-add per stored dimension per row
scored. The distinct rows a query visits are no shape: the configuration
states them (`assumed.walk.visited_rows_per_query`, read from the store's
own gauge `hnsw.visited_fraction` x live rows on the chip), and the metric
`hnsw_visited_fraction` stands beside every traced run to check it. What the
implementation really gathers (rounds x candidate slots a round) is the
gauge `hnsw.gathered_rows_per_query`, and is never counted here.
"""


def work(config: dict, traffic: dict) -> dict:
    dim = config["dimension"]
    recipe = config["index_parameter"]
    deg = 2 * recipe["nlinks"]
    itemsize = {"fp32": 4, "bf16": 2, "sq8": 1}[config["precision"]]
    visited = config["assumed"]["walk"]["visited_rows_per_query"]
    ef = traffic["search_args"].get(
        "ef_search", max(64, recipe["efconstruction"] // 2))
    batch = traffic["batch"]
    row = dim * itemsize
    return {
        "bytes": float(batch) * (visited * (row + deg * 4) + ef * row),
        "flops": 2.0 * dim * batch * (visited + ef),
    }
