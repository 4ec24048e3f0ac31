"""Quickstart: run all six GenGNN models through the one generic engine.

The paper's core claim — a single message-passing architecture serves
GCN / GIN(+VN) / GAT / PNA / DGN unchanged — in ~30 lines.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.configs.gengnn_models import GNN_MODELS, get_gnn_config
from repro.data.pipeline import MOLHIV, MoleculeStream
from repro.gnn import init
from repro.gnn.models import needs_eigvec
from repro.serve.gnn_engine import GNNEngine


def main():
    graphs = MoleculeStream(MOLHIV, seed=0).take(8)  # raw COO, zero preprocessing
    for name in GNN_MODELS:
        cfg = get_gnn_config(name)
        params = init(jax.random.PRNGKey(0), cfg)
        engine = GNNEngine(cfg, params)
        outs, lats, _ = engine.infer_stream(
            [g[:4] for g in graphs], with_eigvec=needs_eigvec(cfg)
        )
        print(f"{name:7s} -> {len(outs)} graphs, "
              f"mean latency {np.mean(lats)*1e6:7.0f} us, "
              f"first output {float(outs[0][0,0]):+.4f}")


if __name__ == "__main__":
    main()
