"""The kernel calls one FQT step makes and the least time of each: the
yardstick of a kernel roofline reader (none is in ``BENCHMARK.json`` until
a chip trace shows the kernels' names)."""

from __future__ import annotations

from bench import kernels


def gemm_sites(m: dict) -> list:
    """(name, k, n, per_layer) of every quantized GEMM of the model."""
    d, ff, hd = m["d_model"], m["d_ff"], m["head_dim"]
    H, KV = m["n_heads"], m["n_kv_heads"]
    sites = [("wq", d, H * hd), ("wk", d, KV * hd), ("wv", d, KV * hd),
             ("wo", H * hd, d)]
    if m["act"] == "swiglu":
        sites += [("gate", d, ff), ("up", d, ff), ("down", ff, d)]
    else:
        sites += [("fc1", d, ff), ("fc2", ff, d)]
    vp = (m["vocab_size"] + m["vocab_pad_to"] - 1) // m["vocab_pad_to"] \
        * m["vocab_pad_to"]
    return [(n, k, nn, True) for n, k, nn in sites] + [("lm_head", d, vp,
                                                        False)]


def fqt_step_calls(m: dict, tokens: int, remat: bool) -> dict:
    """{kernel: [(shape kwargs, calls per step), ...]} of one BHQ FQT step:
    the fused forward (twice per layer under remat), the fused weight
    gradient, and the int8 activation-gradient GEMM on the BHQ codes."""
    L = m["n_layers"]
    calls = {"fused_qlhs_matmul": [], "fused_qboth_tn_matmul": [],
             "q8_matmul": []}
    for _, k, n, layered in gemm_sites(m):
        times = L if layered else 1
        fwd = times * (2 if (remat and layered) else 1)
        calls["fused_qlhs_matmul"].append(({"m": tokens, "k": k, "n": n}, fwd))
        calls["fused_qboth_tn_matmul"].append(({"m": tokens, "k": k, "n": n},
                                               times))
        calls["q8_matmul"].append(({"m": tokens, "k": n, "n": k}, times))
    return calls


def roofline_seconds(kernel: str, shape: dict, peaks: dict) -> float:
    """Least time of one call: the larger of ops over the int8 peak and
    bytes over the HBM bandwidth."""
    mod = kernels.load(kernel)
    return max(mod.ops(**shape) / peaks["int8_ops"],
               mod.bytes(**shape) / peaks["hbm_bytes_per_s"])
