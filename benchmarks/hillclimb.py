"""§Perf hillclimbs: hypothesis -> change -> re-lower -> measure, for the
three selected (arch × shape) pairs — plus the ``overlay`` membership
hillclimb over the sparse planner.

Run AFTER the baseline sweep:
    PYTHONPATH=src python -m benchmarks.hillclimb [pair]

Pairs:
  smollm  — smollm-360m × train_4k × 16x16: most representative of the
            technique (gossip round every step); worst useful-FLOPs fraction
            (replicated 15-head attention).
  stablelm — stablelm-12b × train_4k × 16x16: worst absolute roofline terms;
            collective-bound (fp32 master gossip dominates the wire).
  arctic  — arctic-480b × train_4k × 2x16x16: most collective-bound
            (expert-parallel all-to-all + inter-pod gossip over DCN).
  overlay — greedy membership descent on a k-NN overlay: per round, score
            every candidate single-member eviction by MST cost and keep the
            best. Candidates used to cost a full plan rebuild each; they now
            go through SparsePlanner.replan, and the output reports the
            measured per-edit speedup against timed full-rebuild references.

Each arch variant is a full re-lower + re-compile + roofline extraction;
results accumulate in experiments/perf/<pair>.json for EXPERIMENTS.md §Perf.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import json
import sys

OUT = "experiments/perf"

HILLCLIMBS = {
    "smollm": {
        "arch": "smollm-360m", "shape": "train_4k", "multi_pod": False,
        "variants": [
            ("paper_faithful_dissemination",
             dict(gossip_mode="dissemination")),
            ("baseline_tree_allreduce", dict()),
            ("pad_heads_16",
             dict(arch_overrides=dict(pad_heads_to=16, pad_kv_heads_to=8))),
            ("pad_heads+wire_bf16",
             dict(arch_overrides=dict(pad_heads_to=16, pad_kv_heads_to=8),
                  dfl_overrides=dict(wire_dtype="bfloat16"))),
            ("pad_heads+wire_bf16+no_master",
             dict(arch_overrides=dict(pad_heads_to=16, pad_kv_heads_to=8,
                                      use_master_fp32=False,
                                      optimizer_dtype="bfloat16"),
                  dfl_overrides=dict(wire_dtype="bfloat16"))),
        ],
    },
    "stablelm": {
        "arch": "stablelm-12b", "shape": "train_4k", "multi_pod": False,
        "variants": [
            ("baseline_tree_allreduce", dict()),
            ("wire_bf16", dict(dfl_overrides=dict(wire_dtype="bfloat16"))),
            ("wire_bf16+no_master",
             dict(arch_overrides=dict(use_master_fp32=False),
                  dfl_overrides=dict(wire_dtype="bfloat16"))),
            ("wire_bf16+no_master+microbatch4",
             dict(arch_overrides=dict(use_master_fp32=False, microbatches=4),
                  dfl_overrides=dict(wire_dtype="bfloat16"))),
            ("no_seq_parallel",
             dict(arch_overrides=dict(use_master_fp32=False,
                                      seq_parallel=False))),
            ("no_master+microbatch4+bf16psum",
             dict(arch_overrides=dict(use_master_fp32=False, microbatches=4))),
        ],
    },
    "zamba2": {
        "arch": "zamba2-7b", "shape": "train_4k", "multi_pod": False,
        "variants": [
            # Mamba2's chunked SSD has no sequential variant
            ("baseline_ssd", dict()),
            ("wire_bf16", dict(dfl_overrides=dict(wire_dtype="bfloat16"))),
        ],
    },
    "arctic": {
        "arch": "arctic-480b", "shape": "train_4k", "multi_pod": True,
        "variants": [
            ("baseline_tree_allreduce", dict()),
            ("wire_bf16", dict(dfl_overrides=dict(wire_dtype="bfloat16"))),
            ("bigger_moe_groups",
             dict(arch_overrides=dict(moe_capacity_factor=1.0))),
            ("mixing_gossip", dict(gossip_mode="mixing")),
            ("pad_heads_64",
             dict(arch_overrides=dict(pad_heads_to=64))),
            ("pad_heads_64+cf1.0",
             dict(arch_overrides=dict(pad_heads_to=64, moe_capacity_factor=1.0))),
            ("pad_heads_64+cf1.0+microbatch4",
             dict(arch_overrides=dict(pad_heads_to=64, moe_capacity_factor=1.0,
                                      microbatches=4))),
        ],
    },
}


def run_overlay(n: int = 2000, rounds: int = 4, pool: int = 32,
                timed_refs: int = 4, seed: int = 0) -> dict:
    """Greedy membership hillclimb through the incremental replanner.

    Thin wrapper over :func:`repro.opt.membership_descent` — the edit
    scoring, the ``plan_equal`` double-checks on timed full-rebuild
    references, and the speedup accounting all live in the library; this
    pair only picks the k-NN overlay and writes the JSON artifact.
    """
    from repro.core.graph import TopologySpec, make_topology
    from repro.opt import membership_descent

    g = make_topology(TopologySpec(kind="knn", n=n, seed=seed, k=8,
                                   n_subnets=max(1, n // 100)))
    result = membership_descent(
        g, rounds=rounds, pool=pool, timed_refs=timed_refs, seed=seed,
        log=lambda msg: print(f"[overlay] {msg}"))
    print(f"[overlay] per-edit replan {result['per_edit_replan_ms']}ms vs "
          f"full rebuild {result['per_edit_full_ms']}ms: "
          f"{result['per_edit_speedup']}x")
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "overlay.json"), "w") as f:
        json.dump(result, f, indent=2)
    return result


def run_pair(name: str) -> None:
    if name == "overlay":
        run_overlay()
        return
    from repro.launch.dryrun import dryrun_pair

    spec = HILLCLIMBS[name]
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, f"{name}.json")
    results = []
    if os.path.exists(path):
        results = json.load(open(path))
    done = {r["variant"] for r in results}
    for vname, kw in spec["variants"]:
        if vname in done:
            print(f"[{name}/{vname}] cached")
            continue
        kw = dict(kw)
        mode = kw.pop("gossip_mode", "tree_allreduce")
        r = dryrun_pair(spec["arch"], spec["shape"], spec["multi_pod"],
                        gossip_mode=mode, **kw)
        r["variant"] = vname
        r.pop("memory_analysis", None)
        r.pop("traceback", None)
        results.append(r)
        with open(path, "w") as f:
            json.dump(results, f, indent=2, default=str)


def main() -> None:
    names = sys.argv[1:] or list(HILLCLIMBS) + ["overlay"]
    for n in names:
        run_pair(n)


if __name__ == "__main__":
    main()
