"""Graph data pipelines: neighbor sampler invariants, shape-spec exactness."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import graphs as dgraphs
from repro.graphgen import builder, kronecker


def _graph(scale=10, seed=1):
    return builder.build_csr(kronecker.kronecker_edges(scale, seed=seed), n=1 << scale)


def test_sampled_shape_matches_minibatch_spec():
    """The minibatch_lg cell's static shapes come from the fanout spec."""
    n, m = dgraphs.sampled_shape(1024, (15, 10))
    assert n == 1024 * (1 + 15 + 150) == 169_984
    assert m == 1024 * 15 + 1024 * 15 * 10 == 168_960


def test_neighbor_sampler_block_structure():
    g = _graph()
    sampler = dgraphs.NeighborSampler(g, fanouts=(5, 3), seed=0)
    seeds = np.arange(64)
    nodes, src, dst = sampler.sample(seeds)
    n_expect, m_expect = dgraphs.sampled_shape(64, (5, 3))
    assert nodes.shape == (n_expect,)
    assert src.shape == dst.shape == (m_expect,)
    # layer 0 is exactly the seeds
    np.testing.assert_array_equal(nodes[:64], seeds)
    # message edges point from deeper layer to shallower (src idx > dst idx)
    assert (src > dst).all()
    assert src.max() < n_expect and dst.max() < 64 + 64 * 5


def test_neighbor_sampler_edges_are_real_or_selfloops():
    """Every sampled neighbor is a true graph neighbor (or a self-loop for
    isolated vertices) — the sampler is real, not a stub."""
    g = _graph()
    sampler = dgraphs.NeighborSampler(g, fanouts=(4,), seed=1)
    seeds = np.arange(128)
    nodes, src, dst = sampler.sample(seeds)
    for e in range(src.size):
        parent = nodes[dst[e]]
        child = nodes[src[e]]
        nbrs = g.neighbors(parent)
        assert child in nbrs or (child == parent and nbrs.size == 0), (parent, child)


def test_neighbor_sampler_batch_mask_and_targets():
    g = _graph()
    sampler = dgraphs.NeighborSampler(g, fanouts=(3, 2), seed=2)
    gb = sampler.batch(np.arange(32), d_feat=8)
    n_expect, _ = dgraphs.sampled_shape(32, (3, 2))
    assert gb.nf.shape == (n_expect, 8)
    assert gb.mask.sum() == 32  # loss only on seeds
    assert gb.mask[:32].all() and not gb.mask[32:].any()


@settings(max_examples=10, deadline=None)
@given(n_nodes=st.integers(50, 3000), m_mult=st.integers(1, 8), seed=st.integers(0, 999))
def test_synthetic_graph_exact_shape_property(n_nodes, m_mult, seed):
    """Shape-spec generators hit the requested (n, m) EXACTLY — the 40-cell
    grid depends on it."""
    n_edges = n_nodes * m_mult
    gb = dgraphs.synthetic_graph(n_nodes, n_edges, d_feat=4, seed=seed)
    assert gb.nf.shape == (n_nodes, 4)
    assert gb.src.shape == gb.dst.shape == (n_edges,)
    assert gb.src.max() < n_nodes and gb.dst.max() < n_nodes
    assert gb.src.min() >= 0


def test_sampled_minibatch_trains_end_to_end():
    """The minibatch pipeline: sampler block -> GNN loss/grad (the real
    GraphSAGE-style path behind the minibatch_lg cells)."""
    import jax
    import jax.numpy as jnp

    from repro.models import gnn

    g = _graph()
    sampler = dgraphs.NeighborSampler(g, fanouts=(4, 3), seed=3)
    gb = sampler.batch(np.arange(16), d_feat=8)
    cfg = gnn.GraphCastConfig(n_layers=2, d_hidden=16, d_in=8, d_out=16)
    params = gnn.init(cfg, jax.random.PRNGKey(0))
    graph = gnn.Graph(
        nf=jnp.asarray(gb.nf), src=jnp.asarray(gb.src), dst=jnp.asarray(gb.dst),
        pos=jnp.asarray(gb.pos),
    )
    batch = {"graph": graph, "targets": jnp.asarray(gb.targets),
             "mask": jnp.asarray(gb.mask)}
    loss, grads = jax.value_and_grad(lambda p: gnn.loss_fn(cfg, p, batch))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(grads))


def test_molecule_batch_block_diagonal():
    gb = dgraphs.molecule_batch(n_mols=16, nodes_per=30, edges_per=64, d_feat=16, seed=0)
    assert gb.nf.shape == (480, 16)
    assert gb.src.shape == (1024,)
    # edges never cross molecule boundaries
    assert np.array_equal(gb.src // 30, gb.dst // 30)


def test_kronecker_threaded_draws_equal_one_stream():
    """Edge chunks drawn in threads from advanced PCG64 copies give the
    Graph500 octave reference's edges: each bit's ``m`` row uniforms, then
    its ``m`` column uniforms, from one stream (scale 17 spans 2 chunks)."""
    scale, ef, seed = 17, 16, 9
    n, m = 1 << scale, (1 << scale) * ef
    rng = np.random.default_rng(seed)
    ab = kronecker.A + kronecker.B
    ij = np.zeros((2, m), np.int64)
    for ib in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, kronecker.C / (1 - ab), kronecker.A / ab)
        ij[0] += (1 << ib) * ii
        ij[1] += (1 << ib) * jj
    perm = rng.permutation(n)
    want = perm[ij][:, rng.permutation(m)].T
    np.testing.assert_array_equal(kronecker.kronecker_edges(scale, ef, seed=seed), want)
