"""The plain references equal the program on small graphs."""
import jax.numpy as jnp
import numpy as np

import small  # noqa: F401  (paths and the CPU platform)
from pgbench.gen import kronecker as K
from pgbench.reference import mining as RM
from pgbench.reference import sketch as S

CFG = dict(scale=9, edge_factor=16, a=0.57, b=0.19, c=0.19, structure_seed=1)


def graph(seed=5):
    from repro.core import graph as G

    n = 1 << CFG["scale"]
    edges = K.shuffled(CFG, seed)
    return n, G.from_edge_array(n, edges), K.decode(
        n, K.canonical_keys(n, edges))


def test_bloom_rows_equal_program_build():
    from repro.core import sketches as SK

    n, g, uv = graph()
    for words, b, seed in ((6, 2, 0), (116, 2, 0), (8, 3, 5)):
        want = np.asarray(SK.build_bloom(g, words, b, seed))
        assert np.array_equal(S.bloom_of_graph(n, uv, words, b, seed), want)


def test_estimates_equal_program_card_pass():
    from repro import engine as ENG

    n, g, uv = graph()
    sess = ENG.session(g, "bf", storage_budget=4.0)
    ref = RM.job(n, uv, sess.sketch.data.shape[1], 2, 0, 0.05)
    assert np.array_equal(np.asarray(g.edges), uv)
    np.testing.assert_allclose(np.asarray(sess.edge_cardinalities()),
                               ref["cards"], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(sess.triangle_count()), ref["tc"],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sess.local_clustering()),
                               ref["lcc"], atol=1e-6)
    labels = np.asarray(sess.jarvis_patrick("jaccard", 0.05)[0])
    want = RM.jp_labels_accepted(n, uv, ref["jp_sure"], ref["jp_ambiguous"],
                                 labels.astype(np.int64))
    assert np.array_equal(labels, want)

