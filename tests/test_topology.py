"""Graph module: constructors, MH weight invariants, dynamic sampler,
file I/O, runtime mutation."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.topology import Graph, PeerSampler, circulant_offsets


class TestConstructors:
    def test_ring(self):
        g = Graph.ring(8)
        assert (g.degrees() == 2).all() and g.is_connected()

    def test_fully(self):
        g = Graph.fully_connected(6)
        assert (g.degrees() == 5).all()

    def test_star(self):
        g = Graph.star(7)
        assert g.degrees()[0] == 6 and (g.degrees()[1:] == 1).all()

    @pytest.mark.parametrize("n,d", [(16, 5), (16, 4), (12, 2), (256, 5), (256, 9)])
    def test_regular_circulant(self, n, d):
        g = Graph.regular_circulant(n, d)
        assert (g.degrees() == d).all() and g.is_connected()

    @pytest.mark.parametrize("n,d", [(16, 5), (48, 5), (64, 3)])
    def test_random_regular(self, n, d):
        g = Graph.random_regular(n, d, seed=3)
        assert (g.degrees() == d).all()
        assert not g.adj.diagonal().any()
        assert (g.adj == g.adj.T).all()

    def test_random_regular_varies_with_seed(self):
        gs = [Graph.random_regular(24, 5, s).adj for s in range(4)]
        assert any((gs[0] != g).any() for g in gs[1:])


class TestMetropolisHastings:
    @given(st.integers(4, 64), st.integers(2, 6), st.integers(0, 10))
    @settings(max_examples=25, deadline=None)
    def test_doubly_stochastic(self, n, d, seed):
        d = min(d, n - 1)
        if n * d % 2:
            d -= 1
        if d < 1:
            return
        g = Graph.random_regular(n, d, seed) if d >= 2 else Graph.ring(n)
        W = g.metropolis_hastings()
        assert np.allclose(W.sum(0), 1.0) and np.allclose(W.sum(1), 1.0)
        assert (W >= -1e-12).all()
        assert np.allclose(W, W.T)
        # support = graph edges + diagonal
        off = W.copy()
        np.fill_diagonal(off, 0.0)
        assert ((off > 0) == g.adj).all()

    def test_spectral_gap_ordering(self):
        # denser graphs mix faster: fully > regular(5) > ring
        n = 32
        gaps = [
            Graph.ring(n).spectral_gap(),
            Graph.regular_circulant(n, 5).spectral_gap(),
            Graph.fully_connected(n).spectral_gap(),
        ]
        assert gaps[0] < gaps[1] < gaps[2] + 1e-12

    def test_uniform_weights_row_stochastic(self):
        g = Graph.random_regular(16, 5, 0)
        W = g.uniform_weights()
        assert np.allclose(W.sum(1), 1.0)


class TestDynamicAndIO:
    def test_peer_sampler_changes_every_round(self):
        ps = PeerSampler(32, 5, seed=1)
        g0, g1 = ps.round_graph(0), ps.round_graph(1)
        assert (g0.adj != g1.adj).any()
        assert (g0.degrees() == 5).all() and (g1.degrees() == 5).all()

    def test_edge_list_roundtrip(self, tmp_path):
        g = Graph.random_regular(16, 4, 7)
        p = str(tmp_path / "g.edges")
        g.to_edge_list(p)
        g2 = Graph.from_edge_list(p, 16)
        assert (g.adj == g2.adj).all()

    def test_adjacency_json(self, tmp_path):
        import json

        g = Graph.ring(6)
        d = {str(i): [int(j) for j in g.neighbors(i)] for i in range(6)}
        p = tmp_path / "g.json"
        p.write_text(json.dumps(d))
        g2 = Graph.from_adjacency_json(str(p))
        assert (g.adj == g2.adj).all()

    def test_runtime_mutation(self):
        g = Graph.ring(8)
        g.add_edge(0, 4)
        assert g.adj[0, 4] and g.adj[4, 0]
        g.remove_edge(0, 1)
        assert not g.adj[0, 1]

    def test_circulant_offsets_degree(self):
        assert circulant_offsets(16, 5) == [1, 2, 8]
        assert circulant_offsets(16, 4) == [1, 2]
