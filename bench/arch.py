"""The program's configuration for a benchmark configuration file."""
from __future__ import annotations


def program_config(c: dict):
    """The program's ``ArchConfig`` named by ``c["program_arch"]`` at the file's
    depth; exits when a size or precision differs from the file's."""
    from repro.configs import get_arch

    arch = get_arch(c["program_arch"]).replace(n_layers=c["num_hidden_layers"])
    t = c["training"]
    expect = {
        "d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "resolved_head_dim": c["head_dim"], "vocab": c["vocab_size"],
        "rope_theta": c["rope_theta"], "dtype": t["param_dtype"],
        "optimizer": t["optimizer"], "optimizer_dtype": t["moment_dtype"],
        "use_master_fp32": t["master_dtype"] == "float32", "remat": t["remat"],
    }
    wrong = {k: (getattr(arch, k), v) for k, v in expect.items() if getattr(arch, k) != v}
    if wrong:
        raise SystemExit(f"{c['program_arch']}: the program's config differs from the "
                         f"benchmark's file (program, file): {wrong}")
    return arch
