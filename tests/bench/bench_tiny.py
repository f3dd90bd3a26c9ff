"""Tiny benchmark cells, built from files in a temporary directory: the
harness finds them by name exactly as it finds the committed cells.
Importing this puts the checkout (for ``bench``) and ``src`` on the path."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_XLSTM = {
    "name": "tiny-xlstm", "source": "https://arxiv.org/abs/2405.04517",
    "registry": "xlstm-125m", "reduced": ["n_layers", "d_model"],
    "n_stages": 2, "stages_run": [0, 1], "n_layers": 4, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 0,
    "vocab_size": 256, "block_pattern": ["mlstm", "slstm", "mlstm", "slstm"],
    "ssm": {"state_dim": 8, "chunk": 16}, "rope": "none", "act": "gelu",
    "norm": "layernorm", "causal": True, "tie_embeddings": True,
    "param_dtype": "float32", "compute_dtype": "float32",
    "kernels": "jnp", "boundary_compression": "int8",
}
TINY_SWARM = {
    "name": "tiny-swarm", "source": "https://arxiv.org/abs/2301.11913",
    "registry": "swarm-1b", "reduced": ["n_layers", "d_model"],
    "n_stages": 3, "stages_run": [2], "n_layers": 6, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
    "vocab_size": 256, "rope": "rope", "rope_theta": 10000.0,
    "act": "geglu", "norm": "layernorm", "causal": True, "share_groups": 3,
    "param_dtype": "float32", "compute_dtype": "float32",
    "kernels": "jnp", "boundary_compression": "int8",
}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 0.01}
TRAFFIC = {
    "elastic": {"driver": "swarm_elastic", "peers_per_stage": 2,
                "trainers": 2, "codec": "int8", "quant_block": 64,
                "seq_len": 32, "microbatch": 2, "microbatches_per_step": 4,
                "virtual_horizon_s": 1e6, "optimizer": OPT},
    "stage": {"driver": "stage_loop", "codec": "int8", "quant_block": 64,
              "seq_len": 32, "microbatch": 2, "microbatches_per_step": 2,
              "optimizer": OPT},
}
# float32 on both sides at these sizes: the gaps are round-off
LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 1e-2, "step_change_gap": 1e-2,
          "wire_gx_gap": 1e-2}
CELLS = {
    "tiny-xlstm.elastic": ("tiny-xlstm", "elastic", "xlstm-125m"),
    "tiny-swarm.stage": ("tiny-swarm", "stage", "swarm-1b"),
}


def build_root(path: str) -> str:
    """A checkout holding only the tiny cells' files and the shared
    benchmark code (drivers, readers) copied from this one."""
    bench = os.path.join(path, "bench")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bench, sub))
    shutil.copytree(os.path.join(ROOT, "bench", "drivers"),
                    os.path.join(bench, "drivers"))
    shutil.copytree(os.path.join(ROOT, "bench", "layer_metrics"),
                    os.path.join(bench, "layer_metrics"))
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec = dict(real, configs=[], workloads=[])
    for cfg in (TINY_XLSTM, TINY_SWARM):
        with open(os.path.join(bench, "configs", cfg["name"] + ".json"),
                  "w") as f:
            json.dump(cfg, f)
    for cell, (cfg, traffic, ref) in CELLS.items():
        shutil.copy(os.path.join(ROOT, "bench", "configs", ref + ".py"),
                    os.path.join(bench, "configs", cfg + ".py"))
        with open(os.path.join(bench, "traffic", traffic + ".json"),
                  "w") as f:
            json.dump(TRAFFIC[traffic], f)
        wl = {"config": cfg, "traffic": traffic, "chips": 1,
              "why": "tiny CPU cell", "trace_steps": 1,
              "check": {"steps": 2, "limits": LIMITS}}
        with open(os.path.join(bench, "workloads", cell + ".json"),
                  "w") as f:
            json.dump(wl, f)
        spec["workloads"].append({"name": cell, "config": cfg,
                                  "traffic": traffic, "chips": 1,
                                  "why": "tiny CPU cell"})
        spec["configs"].append({"name": cfg, "source": "tiny",
                                "file": f"bench/configs/{cfg}.json",
                                "reduced": [], "why": "tiny"})
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return path


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return build_root(str(tmp_path_factory.mktemp("tiny-bench")))
