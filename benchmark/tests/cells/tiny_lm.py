"""The language-model cell at a size the CPU holds: the configuration's own
files with the model, the population and the local work made small. The
reference is built from the same ``make(sizes)`` that the harness binds to
the configuration's sizes. Never run on the chip, never a benchmark cell."""

from __future__ import annotations

import copy
import importlib
import types

from benchmark import cells

CONFIG = "lfm2_24b_a2b_ep8"
# 2 dense + 4 expert layers of both kinds, 16 experts in 8 shares of 2, 2 a
# token; a budget the routing overflows now and then at this size
SMALL = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "num_dense_layers": 2, "num_experts": 16, "num_experts_per_tok": 2,
    "experts_held": [0, 2], "conv_L_cache": 3, "norm_eps": 1e-05,
    "rope_theta": 1000000.0, "routed_scaling_factor": 1.0,
    "moe_row_budget": 0.5, "moe_tile_rows": 8, "attention_query_block": 16,
}
SEQ_LEN = 32


def tiny_cell(limits: dict | None = None, forward=None, **sizes) -> dict:
    """``forward`` stands in for the reference's (a planted fault)."""
    small = dict(SMALL, **sizes)
    cfg = copy.deepcopy(cells._json("configs", f"{CONFIG}.json"))
    cfg["model"]["kwargs"] = small
    cfg["population"].update(num_clients=3, vocab_size=small["vocab_size"],
                             seq_len=SEQ_LEN, sequences_per_client=6,
                             topics=4, test_sequences=2)
    cfg.update(batch_size=2, max_batches=2, lr=0.05)
    ref = importlib.import_module(f"benchmark.reference.{CONFIG}")
    init_params, plain = ref.make(small)
    traffic = copy.deepcopy(cells._json("traffic", "silo4_seq2k_block.json"))
    traffic["cohort"] = 3
    names = ["loss_r0", "loss_r1", "dparam", "dparam_med"]
    return {
        "name": f"tiny_{CONFIG}", "chips": 1, "config": cfg,
        "traffic": traffic,
        "limits": limits or {"limits": {n: 1e-3 for n in names}},
        "population": importlib.import_module("benchmark.populations.tokens"),
        "round_reference": importlib.import_module(
            "benchmark.reference.fedavg_round_tokens"),
        "counts": importlib.import_module(f"benchmark.counts.{CONFIG}"),
        "reference": types.SimpleNamespace(
            init_params=init_params, forward=forward or plain, make=ref.make),
        "end_to_end": ["rounds_per_s", "samples_per_s", "setup_s"],
        "per_layer": ["pack_ms_per_round", "train_mfu",
                      "device_busy_ms_per_round", "device_idle_pct",
                      "conv_roofline", "moe_pad_rows_pct"],
    }
