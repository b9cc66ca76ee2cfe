"""Files left after data skipping, as a share (%) of the source's files,
over the window's queries that the traffic sends to a data-skipping
index. Read from the program's index-usage events, whose plan carries
"k/n files after skipping"; such a query with no skipping rewrite read
all of its table's files."""


def read(w):
    kept = total = 0
    for s in w.sent:
        tables = [w.skipping_indexes[i] for i in s.query.expect
                  if i in w.skipping_indexes]
        if not tables:
            continue
        n = w.lake.files[tables[0]]
        if s.skipping:
            kept += sum(k for k, _ in s.skipping)
            total += sum(t for _, t in s.skipping)
        else:
            kept += n
            total += n
    return 100.0 * kept / total if total else None
