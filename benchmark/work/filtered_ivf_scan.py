"""Least work of one filtered IVF_FLAT search request, from the shapes alone.

A client request asks every region of the configuration; this is its work
summed over them. In one region of rows / regions rows the stage is, as in
`ivf_scan.py`, everything between probe selection and the reply's top-k,
whatever kernel implements it, and the union of the batch's probed lists is
taken for independent probes. Of the probed rows only those that satisfy
the predicates need their vectors read and scored: bytes are the matching
share of the probed rows at the stored width, plus every probed row's
columns that the predicates read (8 bytes a value: the columns are int64);
FLOPs one multiply-add per stored dimension per matching probed row per
query. The match share comes from the predicates and the configuration's
column specs (`assumed.corpus.scalars`), columns taken as independent: `eq`
on a categorical of C values keeps 1/C, `in` a list of m values m/C, `ne`
the rest; an order op keeps the part of the column's range it names, half
where the value is drawn per request.
"""

#: bytes of one column value (columns.py makes int64)
COLUMN_BYTES = 8


def _range_share(op: str, value, n: int) -> float:
    """Share of a column uniform over [0, n) that `op value` keeps."""
    if isinstance(value, dict):          # drawn per request, as a row is
        return 0.5 if op in ("lt", "le", "gt", "ge") else 1.0 / n
    v = float(value)
    kept = {"lt": v, "le": v + 1, "gt": n - 1 - v, "ge": n - v}[op]
    return min(1.0, max(0.0, kept / n))


def match_share(predicates, scalars) -> float:
    """Expected share of rows that satisfy every predicate."""
    sizes = {s["field"]: s.get("values", s.get("high")) for s in scalars}
    share = 1.0
    for p in predicates:
        n, op, value = sizes[p["field"]], p["op"], p["value"]
        if op == "in":
            one = len(set(value)) / n
        elif op in ("eq", "ne"):
            one = 1.0 / n
            if op == "ne":
                one = 1.0 - one
        else:
            one = _range_share(op, value, n)
        share *= one
    return share


def work(config: dict, traffic: dict) -> dict:
    regions = config.get("regions", 1)
    rows, dim = config["rows"] / regions, config["dimension"]
    recipe = config["index_parameter"]
    nlist = recipe["ncentroids"]
    args = traffic["search_args"]
    nprobe = args.get("nprobe", recipe["default_nprobe"])
    batch = traffic["batch"]
    itemsize = {"fp32": 4, "bf16": 2, "sq8": 1}[config["precision"]]
    predicates = args.get("predicates", [])
    share = match_share(predicates, config["assumed"]["corpus"]["scalars"])
    fields = len({p["field"] for p in predicates})
    lists = nlist * (1.0 - (1.0 - nprobe / nlist) ** batch)
    rows_per_list = rows / nlist
    probed = lists * rows_per_list
    return {
        "bytes": regions * probed * (share * dim * itemsize
                                     + fields * COLUMN_BYTES),
        "flops": regions * 2.0 * dim * batch * nprobe * rows_per_list
        * share,
    }
