"""The benchmark's cells cut to a size the CPU runs in seconds, for tests.

Only the tests shrink a cell: each cell's widths, depth and batch are
replaced here, and the limits of its check by ones read at this size
(the programs compute in bfloat16, the references in float32, as on the
chip; the gaps are smaller at these widths).
"""
from __future__ import annotations

import copy
import time

from bench import files, harness

SIZES = {
    "phi3-medium-1l": {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
                       "num_key_value_heads": 2, "vocab_size": 256,
                       "program_vocab_pad_multiple": 64},
    "mamba2-1.3b-24l": {"d_model": 64, "n_layer": 2, "vocab_size": 256,
                        "pad_vocab_size_multiple": 16,
                        "layer_defaults": {"d_state": 16, "headdim": 16, "chunk_size": 16}},
}
# between the sound runs' and the faults' (and the fp8 control's) readings
# at these sizes on the CPU, seeds 7, 99 and 123456789012: program
# (loss, grad, update) gaps at most phi3 (3.0e-5, 1.0e-3, 2.9e-4), mamba2
# (5.8e-5, 0.032, 0.0051); fp8 control at least phi3 (9.3e-5, 7.9e-3,
# 2.7e-3), mamba2 loss 4.2e-4, update 0.014; half batch grad at least phi3
# 0.045, mamba2 0.15
LIMITS = {
    "phi3-medium-1l": {"loss_gap": 6e-5, "grad_gap": 4e-3, "update_gap": 1.5e-3, "grad_rule": 1e-3},
    "mamba2-1.3b-24l": {"loss_gap": 2e-4, "grad_gap": 0.1, "update_gap": 0.01, "grad_rule": 1e-3},
}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# Mamba2 has no cell on the chip (the program does not honour its
# configuration, see PERF.md); its reference and the check are tested on
# the phi3 training cell's traffic
STAND_INS = {"mamba2-1.3b-24l.train_ckpt": ("phi3-medium-1l.train_ckpt", "mamba2-1.3b-24l")}


def cell(name: str) -> dict:
    base, config = STAND_INS.get(name, (name, None))
    full = files.resolve(base)
    if config:
        conf = files.load_json("configs", config)
        full = dict(full, entry=dict(full["entry"], name=name, config=config), config=conf,
                    reference=files.load_module("references", conf["reference"]))
    c = copy.deepcopy(full["config"])
    for k, v in SIZES[full["entry"]["config"]].items():
        c[k] = dict(c[k], **v) if isinstance(v, dict) else v
    w = dict(full["workload"], seq_len=32, batch=4, batch_pool=8)
    if "limits" in w:
        w["limits"] = dict(LIMITS[full["entry"]["config"]])
    return dict(full, name=name, config=c, workload=w)


def run(name: str, seed: int = 123456789012, seconds: float = 1.0, fault=None) -> dict:
    return harness.run_cell(cell(name), seed, seconds, False, time.monotonic(), CPU, None, fault)
