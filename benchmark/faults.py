"""Test only (`selftest.py`): SDK clients broken underneath the timed path.
`run.py --wrap-client faults.py:<name>` hands the name to every caller, which
wraps each client it makes; `correct` then has to come out false."""


def alter_answer(client):
    """An answer altered where it is produced: the first id of each reply."""
    search = client.vector_search

    def vector_search(partition, queries, **kw):
        rows = search(partition, queries, **kw)
        if rows and rows[0]:
            rows[0][0] = (rows[0][0][0] + 1, rows[0][0][1])
        return rows

    client.vector_search = vector_search
    return client


def half_batch(client):
    """Half of the batch left out: the reply holds the rest."""
    search = client.vector_search
    client.vector_search = lambda partition, queries, **kw: search(
        partition, queries[:max(1, len(queries) // 2)], **kw)
    return client


def drop_row(client):
    """An acknowledged row that was never written."""
    add = client.vector_add
    client.vector_add = lambda partition, ids, vectors, **kw: add(
        partition, list(ids)[1:], vectors[1:], **kw)
    return client
