"""The four GNN models of the paper (Section 4.1).

  GCN        2 layers                       (node classification)
  GraphSAGE  2 layers, mean aggregation     (node classification)
  GAT        2 layers, 8 heads then 1 head  (node classification),
             attending over N(v) u {v} as published
  GIN        2 convs x 4-layer MLPs = 8 MLP layers + sum-pool readout
             (graph classification)

Every model supports:
  init(key)                         parameter pytree
  apply(params, *edge arrays)       edge-list backend (training/oracle)
  apply_blocked(params, bg, featp)  GHOST blocked backend (serving)
and a `quantized=` flag that routes every combine through the photonic 8-bit
sign-split MVM.  The two backends agree to a float32 tolerance of the
output's scale, not bit for bit (see ``gnn.layers``).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import BlockedGraph
from repro.gnn.layers import GATConv, GCNConv, GINConv, SAGEConv


@dataclasses.dataclass
class GCN:
    f_in: int
    num_classes: int
    hidden: int = 64

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"l1": GCNConv.init(k1, self.f_in, self.hidden),
                "l2": GCNConv.init(k2, self.hidden, self.num_classes)}

    def apply(self, p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False):
        h = GCNConv.apply(p["l1"], feat, edge_src, edge_dst, edge_weight,
                          num_nodes, quantized)
        h = jax.nn.relu(h)
        return GCNConv.apply(p["l2"], h, edge_src, edge_dst, edge_weight,
                             num_nodes, quantized)

    def apply_blocked(self, p, bg: BlockedGraph, feat_padded, quantized=False):
        h = jax.nn.relu(GCNConv.apply_blocked(p["l1"], bg, feat_padded, quantized))
        h = _redistribute(h, bg)
        return GCNConv.apply_blocked(p["l2"], bg, h, quantized)


@dataclasses.dataclass
class GraphSAGE:
    f_in: int
    num_classes: int
    hidden: int = 64

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"l1": SAGEConv.init(k1, self.f_in, self.hidden),
                "l2": SAGEConv.init(k2, self.hidden, self.num_classes)}

    def apply(self, p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False):
        h = SAGEConv.apply(p["l1"], feat, edge_src, edge_dst, None,
                           num_nodes, quantized)
        h = jax.nn.relu(h)
        return SAGEConv.apply(p["l2"], h, edge_src, edge_dst, None,
                              num_nodes, quantized)

    def apply_blocked(self, p, bg, feat_padded, quantized=False):
        h = jax.nn.relu(SAGEConv.apply_blocked(p["l1"], bg, feat_padded, quantized))
        h = _redistribute(h, bg)
        return SAGEConv.apply_blocked(p["l2"], bg, h, quantized)


@dataclasses.dataclass
class GAT:
    f_in: int
    num_classes: int
    hidden: int = 8
    heads: int = 8

    # Layers that run the blocked attention aggregation (engine counters).
    attention_layers = 2

    @staticmethod
    def attended_entries(graph) -> int:
        """Entries one attention layer attends over in ``graph``: its
        edges that are not loops, plus one self term per vertex."""
        loops = int(np.count_nonzero(np.asarray(graph.edge_src)
                                     == np.asarray(graph.edge_dst)))
        return graph.num_edges - loops + graph.num_nodes

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {
            "l1": GATConv.init(k1, self.f_in, self.hidden, self.heads),
            "l2": GATConv.init(k2, self.hidden * self.heads, self.num_classes, 1),
        }

    def apply(self, p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False):
        h = GATConv.apply(p["l1"], feat, edge_src, edge_dst, None, num_nodes,
                          quantized, concat=True)
        h = jax.nn.elu(h)
        return GATConv.apply(p["l2"], h, edge_src, edge_dst, None, num_nodes,
                             quantized, concat=False)

    def apply_blocked(self, p, bg, feat_padded, quantized=False):
        h = jax.nn.elu(GATConv.apply_blocked(p["l1"], bg, feat_padded,
                                             quantized, concat=True))
        h = _redistribute(h, bg)
        return GATConv.apply_blocked(p["l2"], bg, h, quantized, concat=False)


@dataclasses.dataclass
class GIN:
    """2 GIN convs, each with a 4-layer MLP (8 MLP layers total, per the
    paper's 'MLP in GIN was implemented with eight layers'), sum-pool
    readout + linear classifier for graph classification."""

    f_in: int
    num_classes: int
    hidden: int = 32
    mlp_layers: int = 4

    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        from repro.gnn.layers import init_linear
        return {
            "l1": GINConv.init(k1, self.f_in, self.hidden, self.mlp_layers),
            "l2": GINConv.init(k2, self.hidden, self.hidden, self.mlp_layers),
            "out": init_linear(k3, self.hidden, self.num_classes),
        }

    def node_embed(self, p, feat, edge_src, edge_dst, edge_weight, num_nodes,
                   quantized=False):
        h = GINConv.apply(p["l1"], feat, edge_src, edge_dst, None, num_nodes,
                          quantized)
        h = jax.nn.relu(h)
        return GINConv.apply(p["l2"], h, edge_src, edge_dst, None, num_nodes,
                             quantized)

    def apply(self, p, feat, edge_src, edge_dst, edge_weight, num_nodes,
              quantized=False, node_mask=None):
        """Graph-level logits: sum-pool over (valid) nodes, then classify."""
        h = self.node_embed(p, feat, edge_src, edge_dst, edge_weight,
                            num_nodes, quantized)
        if node_mask is not None:
            h = h * node_mask[:, None]
        pooled = h.sum(axis=0)
        return pooled @ p["out"]["w"] + p["out"]["b"]

    def node_embed_blocked(self, p, bg, feat_padded, quantized=False):
        """Blocked node embeddings [G_dst*V, hidden] (pre-readout)."""
        h = jax.nn.relu(GINConv.apply_blocked(p["l1"], bg, feat_padded, quantized))
        h = _redistribute(h, bg)
        return GINConv.apply_blocked(p["l2"], bg, h, quantized)

    def readout(self, p, h_nodes, node_mask=None):
        """Sum-pool valid node embeddings [Nv, hidden] -> class logits.

        Kept separate from the blocked forward so a serving engine can run
        the shape-bucketed embedding batch-wide and the readout per request
        at its true node count (the fp32 sum's value depends on row count).
        """
        if node_mask is not None:
            h_nodes = h_nodes * node_mask[: h_nodes.shape[0], None]
        pooled = h_nodes.sum(axis=0)
        return pooled @ p["out"]["w"] + p["out"]["b"]

    def apply_blocked(self, p, bg, feat_padded, quantized=False,
                      node_mask=None):
        h = self.node_embed_blocked(p, bg, feat_padded, quantized)
        return self.readout(p, h[:bg.num_nodes], node_mask)


def _redistribute(h_dst: jax.Array, bg: BlockedGraph) -> jax.Array:
    """Re-pad a destination-side activation [G_dst*V, F] to the source-side
    padding [G_src*N, F] for the next layer's tile loads."""
    pad_src = bg.num_src_groups * bg.n
    valid = h_dst[:bg.num_nodes]
    need = pad_src - valid.shape[0]
    return jnp.pad(valid, ((0, need), (0, 0)))


def build_model(name: str, f_in: int, num_classes: int, **kw):
    name = name.lower()
    if name == "gcn":
        return GCN(f_in, num_classes, **kw)
    if name in ("graphsage", "sage", "gs"):
        return GraphSAGE(f_in, num_classes, **kw)
    if name == "gat":
        return GAT(f_in, num_classes, **kw)
    if name == "gin":
        return GIN(f_in, num_classes, **kw)
    raise KeyError(f"unknown GNN model '{name}'")
