"""Least work of one IVF_FLAT scan call, from the stage's shapes alone.

The stage is everything between probe selection and the reply's top-k,
whatever kernel implements it. Bytes: the rows of the union of the batch's
probed lists, each list read once, at the stored width. The union is taken
for probes that fall on lists independently (nlist * (1 - (1 - nprobe/nlist)
** batch) lists of rows/nlist rows each): queries that share lists make the
true union smaller, so the share of the roofline read from this is an upper
reading of the true one, never a lower. FLOPs: one multiply-add per stored
dimension per probed row per query. Exact dimension pruning may skip bytes
counted here (PERF.md, Open questions).
"""


def work(config: dict, traffic: dict) -> dict:
    rows, dim = config["rows"], config["dimension"]
    recipe = config["index_parameter"]
    nlist = recipe["ncentroids"]
    nprobe = traffic["search_args"].get("nprobe", recipe["default_nprobe"])
    batch = traffic["batch"]
    itemsize = {"fp32": 4, "bf16": 2, "sq8": 1}[config["precision"]]
    lists = nlist * (1.0 - (1.0 - nprobe / nlist) ** batch)
    rows_per_list = rows / nlist
    return {
        "bytes": lists * rows_per_list * dim * itemsize,
        "flops": 2.0 * dim * batch * nprobe * rows_per_list,
    }
