"""Unit tests for kernels/helpers added in round 3: dense_rank,
change_mask, null-aware sort keys, the a2a exchange primitive, multi-key
composite packing, and the hybrid-merge position math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.execution.columnar import Column, Table
from hyperspace_tpu.ops import kernels


class TestDenseRank:
    def test_matches_numpy_single_key(self):
        rng = np.random.default_rng(1)
        a = rng.integers(-50, 50, 500).astype(np.int64)
        ranks = np.asarray(kernels.dense_rank([jnp.asarray(a)]))
        # Equal values ⇔ equal ranks; order-preserving.
        _, exp = np.unique(a, return_inverse=True)
        assert np.array_equal(ranks - ranks.min(), exp)

    def test_matches_numpy_multi_key(self):
        rng = np.random.default_rng(2)
        a = rng.integers(0, 10, 300).astype(np.int64)
        b = rng.integers(0, 7, 300).astype(np.int64)
        ranks = np.asarray(kernels.dense_rank(
            [jnp.asarray(a), jnp.asarray(b)]))
        tuples = list(zip(a.tolist(), b.tolist()))
        uniq = {t: i for i, t in enumerate(sorted(set(tuples)))}
        exp = np.array([uniq[t] for t in tuples])
        assert np.array_equal(ranks - ranks.min(), exp)

    def test_empty(self):
        assert kernels.dense_rank([jnp.zeros(0, jnp.int64)]).shape == (0,)

    def test_join_on_ranks_equals_join_on_tuples(self):
        rng = np.random.default_rng(3)
        la = rng.integers(0, 6, 100).astype(np.int64)
        lb = rng.integers(0, 4, 100).astype(np.int64)
        ra = rng.integers(0, 6, 40).astype(np.int64)
        rb = rng.integers(0, 4, 40).astype(np.int64)
        keys = [jnp.asarray(np.concatenate([la, ra])),
                jnp.asarray(np.concatenate([lb, rb]))]
        ranks = kernels.dense_rank(keys)
        lk, rk = ranks[:100], ranks[100:]
        order = kernels.lex_sort_indices([rk])
        li, ri = kernels.merge_join_indices(lk, jnp.take(rk, order))
        got = len(li)
        exp = sum((la[i] == ra[j]) and (lb[i] == rb[j])
                  for i in range(100) for j in range(40))
        assert got == exp


class TestChangeMask:
    def test_boundaries(self):
        a = jnp.asarray(np.array([1, 1, 2, 2, 2, 5], np.int64))
        m = np.asarray(kernels.change_mask([a]))
        assert m.tolist() == [False, False, True, False, False, True]

    def test_multi_key_changes(self):
        a = jnp.asarray(np.array([1, 1, 1, 2], np.int64))
        b = jnp.asarray(np.array([7, 8, 8, 8], np.int64))
        m = np.asarray(kernels.change_mask([a, b]))
        assert m.tolist() == [False, True, False, True]


class TestNullAwareKeys:
    def test_null_first_ordering(self):
        from hyperspace_tpu.execution.executor import _null_aware_keys

        data = jnp.asarray(np.array([5, 0, -3, 7], np.int64))
        validity = jnp.asarray(np.array([True, False, True, True]))
        keys = _null_aware_keys(Column("int64", data, validity))
        order = np.asarray(kernels.lex_sort_indices(keys))
        # Null row (index 1) first, then -3, 5, 7.
        assert order.tolist() == [1, 2, 0, 3]

    def test_non_nullable_passthrough(self):
        from hyperspace_tpu.execution.executor import _null_aware_keys

        data = jnp.asarray(np.array([3, 1], np.int64))
        keys = _null_aware_keys(Column("int64", data, None))
        assert len(keys) == 1


class TestPack2:
    def test_negative_second_key_order(self):
        a = jnp.asarray(np.array([0, 0, 0], np.int32))
        b = jnp.asarray(np.array([-5, 0, 5], np.int32))
        packed = np.asarray(kernels.pack2_int32(a, b))
        assert packed.tolist() == sorted(packed.tolist())


class TestA2AExchange:
    def test_rows_land_on_hashed_owner(self):
        """Every valid row must arrive exactly once, on the device its key
        hashes to."""
        from hyperspace_tpu.execution.spmd import _a2a_exchange
        from hyperspace_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                                  pad_and_shard)
        from jax.sharding import PartitionSpec as P

        n_dev = len(jax.devices())
        rng = np.random.default_rng(4)
        n = 512
        keys = rng.integers(0, 1000, n).astype(np.int64)
        payload = np.arange(n, dtype=np.int64)
        mesh = make_mesh()
        arrays, valid = pad_and_shard(
            mesh, {"k": jnp.asarray(keys), "p": jnp.asarray(payload)}, n)
        cap = n  # plenty

        def per_device(arrays, valid):
            dst = (kernels.hash32_values(arrays["k"], "int64")
                   % np.uint32(n_dev)).astype(jnp.int32)
            recv, rvalid, of, _need = _a2a_exchange(
                arrays, valid, dst, n_dev, cap)
            return recv["k"], recv["p"], rvalid, of

        from hyperspace_tpu.parallel.sharding import device_view
        k_r, p_r, v_r, of = device_view(
            per_device, mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P()))(
                arrays, valid)
        assert int(of) == 0
        k_r = np.asarray(k_r)
        p_r = np.asarray(p_r)
        v_r = np.asarray(v_r)
        # Exactly the n valid rows arrived, each payload exactly once.
        assert v_r.sum() == n
        assert sorted(p_r[v_r].tolist()) == payload.tolist()
        # Owner check: the device block a row sits in == hash(key) % n_dev.
        rows_per_dev = len(v_r) // n_dev
        for i in np.nonzero(v_r)[0]:
            dev = i // rows_per_dev
            h = kernels.hash32_value_host(int(k_r[i]), "int64")
            assert h % n_dev == dev

    def test_overflow_flag_on_tiny_cap(self):
        from hyperspace_tpu.execution.spmd import _a2a_exchange
        from hyperspace_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                                  pad_and_shard)
        from jax.sharding import PartitionSpec as P

        n_dev = len(jax.devices())
        n = 256
        keys = np.full(n, 7, np.int64)  # all rows to one device
        mesh = make_mesh()
        arrays, valid = pad_and_shard(mesh, {"k": jnp.asarray(keys)}, n)

        def per_device(arrays, valid):
            dst = (kernels.hash32_values(arrays["k"], "int64")
                   % np.uint32(n_dev)).astype(jnp.int32)
            _, _, of, need = _a2a_exchange(arrays, valid, dst, n_dev, 2)
            return (of, need)

        from hyperspace_tpu.parallel.sharding import device_view
        (of, need) = device_view(
            per_device, mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P()))(arrays, valid)
        assert int(of) == 1
        # The reported need is the exact worst block: every row of the
        # biggest shard targets one destination.
        rows_per_dev = -(-n // n_dev)
        assert int(need) == rows_per_dev


class TestMultiKeyComposite:
    def test_packed_composite_equality_is_exact(self):
        from hyperspace_tpu.execution.spmd import (_prepare_broadcast,
                                                   _stream_probe_key)

        rng = np.random.default_rng(5)
        ra = rng.integers(10, 20, 30).astype(np.int64)
        rb = rng.integers(-3, 3, 30).astype(np.int64)
        right = Table({
            "ra": Column("int64", jnp.asarray(ra)),
            "rb": Column("int64", jnp.asarray(rb)),
            "val": Column("int64", jnp.asarray(np.arange(30, dtype=np.int64))),
        })
        # Deduplicate (broadcast side must be unique on the key).
        seen = {}
        for i, t in enumerate(zip(ra.tolist(), rb.tolist())):
            seen.setdefault(t, i)
        keep = np.zeros(30, bool)
        keep[list(seen.values())] = True
        right = right.filter(jnp.asarray(keep))

        la = rng.integers(0, 30, 200).astype(np.int64)  # incl. out-of-range
        lb = rng.integers(-6, 6, 200).astype(np.int64)
        tiny = {"la": Column("int64", jnp.asarray(la)),
                "lb": Column("int64", jnp.asarray(lb))}
        # No stream rows to budget a position table: the side searches.
        side = _prepare_broadcast(right, [("la", "ra"), ("lb", "rb")], tiny,
                                  stream_rows=0)
        probe_table = Table({"la": tiny["la"], "lb": tiny["lb"]})
        lk, valid = _stream_probe_key(
            probe_table, [("la", "ra"), ("lb", "rb")], side.pack)
        idx = jnp.searchsorted(side.keys, lk)
        idx_c = jnp.minimum(idx, side.keys.shape[0] - 1)
        found = np.asarray(jnp.take(side.keys, idx_c) == lk)
        rset = set(zip(np.asarray(side.table.column("ra").data).tolist(),
                       np.asarray(side.table.column("rb").data).tolist()))
        exp = np.array([(x, y) in rset for x, y in zip(la, lb)])
        assert np.array_equal(found, exp)


class TestHybridMergePositions:
    def test_two_way_merge_is_a_permutation(self):
        rng = np.random.default_rng(6)
        a = np.sort(rng.integers(0, 100, 50))
        b = np.sort(rng.integers(0, 100, 20))
        ja, jb = jnp.asarray(a), jnp.asarray(b)
        pos_a = np.arange(50) + np.asarray(
            jnp.searchsorted(jb, ja, side="left"))
        pos_b = np.arange(20) + np.asarray(
            jnp.searchsorted(ja, jb, side="right"))
        allpos = np.concatenate([pos_a, pos_b])
        assert sorted(allpos.tolist()) == list(range(70))
        merged = np.empty(70, np.int64)
        merged[pos_a] = a
        merged[pos_b] = b
        assert np.array_equal(merged, np.sort(np.concatenate([a, b]),
                                              kind="stable"))


class TestMergeJoinProperty:
    def test_random_joins_match_naive(self):
        """Property: merge_join_indices over random multisets equals the
        naive nested-loop pairing, across sizes incl. empty and skew."""
        import numpy as np
        import jax.numpy as jnp
        from hyperspace_tpu.ops import kernels

        for seed in (0, 1, 2, 3):
            rng = np.random.default_rng(seed)
            n_l = int(rng.integers(0, 300))
            n_r = int(rng.integers(0, 300))
            left = rng.integers(-20, 20, n_l).astype(np.int64)
            right = np.sort(rng.integers(-20, 20, n_r).astype(np.int64))
            li, ri = kernels.merge_join_indices(
                jnp.asarray(left), jnp.asarray(right))
            got = sorted(zip(np.asarray(li).tolist(),
                             np.asarray(ri).tolist()))
            naive = sorted((i, j) for i in range(n_l) for j in range(n_r)
                           if left[i] == right[j])
            assert got == naive, f"seed {seed}"
