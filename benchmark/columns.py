"""Scalar columns of a configuration's rows, and the predicates of a filtered
search over them. One generator for the whole harness: `caller.py` (the
load, the fresh rows of a write, each request's drawn values) and
`reference.py` (the truth of a filtered request) both import it. Imports
nothing of the program.

`assumed.corpus.scalars` lists the columns, each `{field, kind, ...}`:

  categorical  `values` C   int64, uniform in [0, C)
  uniform_int  `high` H     int64, uniform in [0, H)

Each column is made per block of `block_rows` rows from the seed, on a
stream of its own, so a configuration's vectors, queries and fresh rows are
the same bits with columns as without. A configuration that states no
`scalars` has none, and nothing of them reaches the wire.

A traffic file's `search_args.predicates` is a list of `{field, op, value}`:
`op` one of `OPS`, `value` a number, a list (`in`), or `{"draw": <kind>}`:
a value drawn per request, as one row of that field's column is, from the
seed, the caller and the request's number.
"""

from __future__ import annotations

import numpy as np

# rng stream tags; reference.py holds 1-4, run.py 11 and 13, caller.py 7
SCALARS, FRESH_SCALARS, DRAWS = 5, 6, 8
#: a column's kind -> the key that states its number of values
KINDS = {"categorical": "values", "uniform_int": "high"}
#: the `search_args` of a filtered search (`filter` and `filter_type` by
#: their pb names)
FILTER_ARGS = ("filter", "filter_type", "predicates")
OPS = {"eq": np.equal, "ne": np.not_equal, "lt": np.less, "le": np.less_equal,
       "gt": np.greater, "ge": np.greater_equal, "in": np.isin}


class Unsupported(ValueError):
    """The configuration states something no arm of the reference honours."""


def specs(config: dict) -> list:
    """The configuration's columns, each checked. -> [{field, kind, ...}]"""
    out, seen = [], set()
    for spec in config["assumed"]["corpus"].get("scalars", []):
        field, kind = spec.get("field"), spec.get("kind")
        if kind not in KINDS:
            raise Unsupported(f"scalar column {field!r}: kind {kind!r}; the "
                              f"generator knows {sorted(KINDS)}")
        n = spec.get(KINDS[kind])
        if not isinstance(field, str) or not field or field in seen:
            raise Unsupported(f"scalar column {field!r}: no field, or twice")
        if not isinstance(n, int) or n < 1:
            raise Unsupported(f"scalar column {field!r}: {KINDS[kind]} {n!r} "
                              "is no whole number >= 1")
        seen.add(field)
        out.append(spec)
    return out


def _column(spec: dict, rng, n: int) -> np.ndarray:
    return rng.integers(0, spec[KINDS[spec["kind"]]], n, dtype=np.int64)


class Columns:
    """The columns of one seed and configuration, block by block."""

    def __init__(self, seed: int, config: dict):
        self.seed, self.specs = seed, specs(config)
        self.rows = config["rows"]
        self.block_rows = int(config["assumed"]["corpus"]["block_rows"])

    def __bool__(self) -> bool:
        return bool(self.specs)

    def _make(self, tag: int, unit: int, n: int) -> dict:
        return {s["field"]: _column(
            s, np.random.default_rng([tag, self.seed, unit, i]), n)
            for i, s in enumerate(self.specs)}

    def block(self, b: int) -> dict:
        """{field: int64[n]} of rows [b * block_rows, ...), as
        `reference.Data.block(b)`."""
        n = min(self.rows, (b + 1) * self.block_rows) - b * self.block_rows
        return self._make(SCALARS, b, n)

    def fresh(self, request: int, batch: int) -> dict:
        """{field: int64[batch]} of write request `request`'s rows."""
        return self._make(FRESH_SCALARS, request, batch)

    def corpus(self) -> dict:
        blocks = [self.block(b) for b in range(-(-self.rows // self.block_rows))]
        return {s["field"]: np.concatenate([c[s["field"]] for c in blocks])
                for s in self.specs}


def as_rows(cols: dict) -> list:
    """{field: values} -> one {field: int} a row: the SDK's `scalars`."""
    fields = list(cols)
    return [dict(zip(fields, map(int, vals)))
            for vals in zip(*(cols[f] for f in fields))]


def concat(parts) -> dict:
    """Columns of several row ranges, one after the other."""
    parts = list(parts)
    return {f: np.concatenate([p[f] for p in parts]) for f in parts[0]}


# --------------------------------------------------------------- predicates
def check_predicates(predicates, config: dict) -> None:
    """Refuses what the reference could not judge: no predicate, an unknown
    op, a field the configuration's columns lack, a draw of another kind
    than the field's, a value of the wrong shape for its op."""
    by_field = {s["field"]: s for s in specs(config)}
    if not isinstance(predicates, list) or not predicates:
        raise Unsupported("a filtered search states no predicates")
    for p in predicates:
        field, op, value = p.get("field"), p.get("op"), p.get("value")
        if op not in OPS:
            raise Unsupported(f"predicate op {op!r}; the reference knows "
                              f"{sorted(OPS)}")
        if field not in by_field:
            raise Unsupported(f"predicate on {field!r}: the configuration's "
                              f"scalars are {sorted(by_field)}")
        if isinstance(value, dict):
            if value.get("draw") != by_field[field]["kind"] or op == "in":
                raise Unsupported(
                    f"predicate on {field!r}: draw {value!r} is not its "
                    f"kind {by_field[field]['kind']!r}, or under `in`")
        elif not (all(map(_number, value)) if op == "in" and isinstance(
                value, list) else op != "in" and _number(value)):
            raise Unsupported(f"predicate {p}: `in` takes a list of numbers, "
                              "the other ops a number")


def _number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def fill(predicates, config: dict, seed: int, caller: int,
         request: int) -> list:
    """The predicates with each `{"draw": kind}` replaced by a value drawn
    as one row of that field's column is, from the request's own stream."""
    by_field = {s["field"]: s for s in specs(config)}
    rng = np.random.default_rng([DRAWS, seed, caller, request])
    return [dict(p, value=int(_column(by_field[p["field"]], rng, 1)[0]))
            if isinstance(p["value"], dict) else dict(p)
            for p in predicates]


def matches(cols: dict, predicates) -> np.ndarray:
    """bool[rows]: the rows whose columns satisfy every predicate."""
    n = len(next(iter(cols.values())))
    keep = np.ones(n, bool)
    for p in predicates:
        keep &= OPS[p["op"]](cols[p["field"]], p["value"])
    return keep
