"""Test only (`selftest.py`): SDK clients broken underneath the timed path.
`run.py --wrap-client faults.py:<name>` hands the name to every caller, which
wraps each client it makes (`<name>(client, spec)`, `spec` the caller's own:
seed, configuration, traffic); `correct` then has to come out false, but
for `apply_predicates`, the one sound program here."""


def alter_answer(client, _spec=None):
    """An answer altered where it is produced: the first id of each reply."""
    search = client.vector_search

    def vector_search(partition, queries, **kw):
        rows = search(partition, queries, **kw)
        if rows and rows[0]:
            rows[0][0] = (rows[0][0][0] + 1, rows[0][0][1])
        return rows

    client.vector_search = vector_search
    return client


def half_batch(client, _spec=None):
    """Half of the batch left out: the reply holds the rest."""
    search = client.vector_search
    client.vector_search = lambda partition, queries, **kw: search(
        partition, queries[:max(1, len(queries) // 2)], **kw)
    return client


def drop_row(client, _spec=None):
    """An acknowledged row that was never written."""
    add = client.vector_add
    client.vector_add = lambda partition, ids, vectors, **kw: add(
        partition, list(ids)[1:], vectors[1:], **kw)
    return client


def _unfiltered(kw: dict) -> dict:
    import columns

    return {a: v for a, v in kw.items() if a not in columns.FILTER_ARGS}


def drop_predicates(client, _spec=None):
    """A filtered search that reaches the store without its filter: the
    reply is the unfiltered top-k."""
    search = client.vector_search
    client.vector_search = lambda partition, queries, **kw: search(
        partition, queries, **_unfiltered(kw))
    return client


def apply_predicates(client, spec):
    """A program that honours the predicates whatever the store does with
    them: the unfiltered top-(10 k), kept where the row's columns (the
    harness's own, from the seed, corpus rows only) satisfy every one."""
    import columns
    import reference

    cols = reference.Data(spec["seed"], spec["config"]).columns.corpus()
    search = client.vector_search

    def vector_search(partition, queries, topk=10, predicates=None, **kw):
        if not predicates:
            return search(partition, queries, topk=topk, **kw)
        keep = columns.matches(cols, predicates)
        rows = search(partition, queries, topk=10 * topk, **_unfiltered(kw))
        return [[(i, d) for i, d in row if i < len(keep) and keep[i]][:topk]
                for row in rows]

    client.vector_search = vector_search
    return client
