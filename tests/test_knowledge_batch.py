"""Knowledge-base caching/batched-query + batched KNN kernel tests."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.knowledge import KnowledgeBase
from repro.kernels import knn, ref


def _mk_kb(n=80, d=13, seed=0, **kw):
    rng = np.random.default_rng(seed)
    states = np.abs(rng.normal(size=(n, d)))
    kb = KnowledgeBase(**kw)
    kb.add_window(states, rng.integers(0, 100, n), rng.uniform(0, 1, n))
    return kb, states


class TestQueryCache:
    @pytest.mark.parametrize("backend", [
        "numpy", "jax", pytest.param("pallas", marks=pytest.mark.pallas)])
    def test_cached_matches_uncached(self, backend):
        kb_c, states = _mk_kb(backend=backend, cache=True)
        kb_u, _ = _mk_kb(backend=backend, cache=False)
        q = states[5] + 0.03
        for a, b in zip(kb_c.query(q, k=4), kb_u.query(q, k=4)):
            np.testing.assert_array_equal(a, b)

    def test_cache_invalidated_on_add_window(self):
        kb, states = _mk_kb(backend="numpy")
        kb.query(states[0], k=1)               # builds the cache
        rng = np.random.default_rng(99)
        new = np.abs(rng.normal(size=(40, states.shape[1]))) + 50.0
        kb.add_window(new, np.full(40, 777.0), np.ones(40))
        assert len(kb) == 120
        m, _, d = kb.query(new[3], k=1)
        assert m[0] == 777.0 and d[0] < 1e-6

    def test_device_cache_built_for_jax_backend(self):
        kb, states = _mk_kb(backend="jax")
        kb.query(states[0], k=2)
        assert kb._Xn is not None and kb._Xn_dev is not None
        np.testing.assert_allclose(np.asarray(kb._Xn_dev),
                                   kb._Xn.astype(np.float32), rtol=1e-6)


class TestQueryBatch:
    @pytest.mark.parametrize("backend", [
        "numpy", "jax", pytest.param("pallas", marks=pytest.mark.pallas)])
    def test_batch_rows_match_single_queries(self, backend):
        kb, states = _mk_kb(backend=backend)
        rng = np.random.default_rng(1)
        queries = states[:16] + rng.normal(scale=0.05, size=(16, states.shape[1]))
        m_b, rho_b, d_b = kb.query_batch(queries, k=4)
        assert m_b.shape == rho_b.shape == d_b.shape == (16, 4)
        for i, q in enumerate(queries):
            m_s, rho_s, d_s = kb.query(q, k=4)
            np.testing.assert_allclose(d_b[i], d_s, rtol=1e-4, atol=1e-4)
            # ties may reorder between the fused and dot-form distances;
            # compare the neighbour decision sets
            np.testing.assert_allclose(np.sort(m_b[i]), np.sort(m_s), rtol=1e-6)

    def test_single_state_is_promoted_to_batch(self):
        kb, states = _mk_kb(backend="numpy")
        m, rho, d = kb.query_batch(states[7], k=3)
        assert m.shape == (1, 3)
        assert d[0, 0] < 1e-6


@pytest.mark.pallas
class TestBatchedKernel:
    def test_batch_distances_match_reference(self):
        rng = np.random.default_rng(3)
        cases = jnp.asarray(rng.normal(size=(300, 17)), jnp.float32)
        queries = jnp.asarray(rng.normal(size=(33, 17)), jnp.float32)
        d2 = np.asarray(knn.squared_distances_batch(cases, queries))
        expect = np.sum((np.asarray(queries)[:, None, :]
                         - np.asarray(cases)[None, :, :]) ** 2, axis=2)
        np.testing.assert_allclose(d2, expect, rtol=1e-4, atol=1e-4)

    def test_batch_topk_matches_per_row_reference(self):
        rng = np.random.default_rng(4)
        cases = jnp.asarray(rng.normal(size=(150, 9)), jnp.float32)
        queries = jnp.asarray(rng.normal(size=(7, 9)), jnp.float32)
        dist, idx = knn.knn_topk_batch(cases, queries, 5)
        assert dist.shape == idx.shape == (7, 5)
        for i in range(7):
            d_r, _ = ref.knn_topk_ref(cases, queries[i], 5)
            np.testing.assert_allclose(np.asarray(dist)[i], np.asarray(d_r),
                                       rtol=1e-4, atol=1e-4)

    def test_padding_never_wins(self):
        # N and Q far from the block sizes: padded rows/cols must not
        # surface in the top-k
        rng = np.random.default_rng(5)
        cases = jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)
        queries = jnp.asarray(rng.normal(size=(2, 3)), jnp.float32)
        dist, idx = knn.knn_topk_batch(cases, queries, 5)
        assert int(np.asarray(idx).max()) < 5
        assert np.isfinite(np.asarray(dist)).all()

    def test_interpret_auto_detect(self):
        import jax

        expected = jax.default_backend() != "tpu"
        assert knn.default_interpret() is expected


@pytest.mark.parametrize("path", ["pallas", "jax"])
def test_batch_cross_term_runs_at_full_f32_precision(path):
    """A TPU's default f32 matmul is one bf16 pass (~0.1 error in d^2 on
    normalised Table-2 features); both device paths of the batched
    distance must ask for HIGHEST, which the CPU cannot show numerically."""
    import jax

    from repro.core.knowledge import _knn_jax_batch

    cases = jnp.ones((300, 23), jnp.float32)
    queries = jnp.ones((10, 23), jnp.float32)
    if path == "pallas":
        fn = lambda c, q: knn._squared_distances_batch(c, q, interpret=True)  # noqa: E731
    else:
        fn = lambda c, q: _knn_jax_batch(c, q, k=3)  # noqa: E731
    jaxpr = str(jax.make_jaxpr(fn)(cases, queries))
    assert "dot_general" in jaxpr
    assert "DEFAULT" not in jaxpr and "HIGHEST" in jaxpr
