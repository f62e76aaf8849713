"""Small cells for the tests: the benchmark's cells, cut so that a CPU runs
them in seconds, with the program's config cut to the same sizes."""
from __future__ import annotations

import copy

import harness

TINY_SIZES = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
              "vocab_size": 200}
PROGRAM_SIZES = {"d_model": 64, "d_ff": 128, "n_layers": 2, "n_heads": 4,
                 "n_kv_heads": 2, "head_dim": 16, "vocab": 200}

# The cells' limits hold for their own sizes. At these sizes the sound bfloat16
# step reads gaps up to about 1e-3 against the float32 reference (fp8 control
# 1e-2, half the batch 2e-2 and more), and a sound gossip round about 1e-7
# (bf16 mean 1e-2), so a tiny cell is held to these.
TINY_LIMITS = {"train": {"loss_gap": 0.02, "grad_norm_gap": 0.005, "update_norm_gap": 0.005},
               "gossip": {"mean_gap": 1e-4}}
END_TO_END = {"train": ["train_tokens_per_s"], "gossip": ["gossip_round_ms", "gossip_round_ms_p90"]}

# The gossip cell waits for a four-chip measurement (PERF.md, Open questions);
# its driver, reference and readers are tested here under this entry.
GOSSIP = {"name": "smollm-360m.gossip.diss4", "config": "smollm-360m",
          "traffic": "gossip.diss4", "chips": 4}


def cell(workload: dict, monkeypatch) -> harness.Cell:
    """``workload``'s cell at the tiny sizes, held to ``TINY_LIMITS``; the
    program's registry gives the same sizes for the configuration's arch while
    the test runs."""
    import repro.configs as configs

    b = harness.load_json(harness.ROOT / "BENCHMARK.json")
    config = next(c for c in b["configs"] if c["name"] == workload["config"])
    config = dict(harness.load_json(harness.ROOT / config["file"]), **TINY_SIZES)
    traffic = harness.load_json(harness.BENCH / "traffic" / f"{workload['traffic']}.json")
    driver = traffic["driver"]
    if driver == "train":
        traffic = dict(traffic, seq_len=32, batch_per_node=4, pool=4)
    small = configs.get_arch(config["program_arch"]).replace(**PROGRAM_SIZES)
    monkeypatch.setattr(configs, "get_arch", lambda _: small)
    end_to_end = [{"name": n, "unit": "-"} for n in END_TO_END[driver]
                  + ["peak_hbm_gib", "setup_s"]]
    return harness.Cell(name=workload["name"], chips=workload["chips"],
                        config_name=workload["config"], config=copy.deepcopy(config),
                        traffic_name=workload["traffic"], traffic=traffic,
                        end_to_end=end_to_end, per_layer=[],
                        limits=dict(TINY_LIMITS[driver]))
