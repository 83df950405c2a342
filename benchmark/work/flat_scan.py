"""Least work of one FLAT (exact) scan call: every live row read once at the
stored width, one multiply-add per stored dimension per row per query."""


def work(config: dict, traffic: dict) -> dict:
    rows, dim = config["rows"], config["dimension"]
    batch = traffic["batch"]
    itemsize = {"fp32": 4, "bf16": 2, "sq8": 1}[config["precision"]]
    return {
        "bytes": float(rows) * dim * itemsize,
        "flops": 2.0 * dim * rows * batch,
    }
