"""Buffer-pool hits over probes (%) in the window, from
``BufferPool.stats()`` before and after it (execution/buffer_pool.py)."""


def read(w):
    hits = w.pool_after["hits"] - w.pool_before["hits"]
    misses = w.pool_after["misses"] - w.pool_before["misses"]
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
