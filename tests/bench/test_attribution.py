"""Self-time attribution of the traced step (``bench/attribution.py``) and
its three readers, on hand-built profiles reduced by ``bench/trace.py``;
the kernel names it matches against the program's one table."""

import os
import types

import pytest

from bench import attribution, readers, run, trace
from conftest import ROOT, TEST_BENCH, TRAIN_TRAFFIC


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _reduce(ops, window=(0, 1000)):
    """Reduce a one-device profile whose ``XLA Ops`` line holds ``ops``
    (name, start, duration, tf_op)."""
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python", events=[
            _ev("bench.trace_window", window[0], window[1] - window[0])])])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[
            _ev(n, s, d, **({"tf_op": t} if t else {}))
            for n, s, d, t in ops])])
    return trace.reduce_profile(types.SimpleNamespace(planes=[host, dev]))


Q = "jit(step_fn)/transpose(jvp())/while/body/closed_call/q[layers.mlp.fc1|agrad]/sort"
ATTN = "jit(step_fn)/jvp()/while/body/closed_call/fp[attn.sdpa]/exp"
GEMM = "jit(step_fn)/jvp()/q[layers.mlp.fc1|fwd]/fused_qlhs_matmul/pallas_call"

# each case: ops, then the expected self seconds (ns) per category and of
# the containers' own time
NESTED = {
    "while_holds_two_ops": (
        [("while.269", 100, 600, "jit(step_fn)/transpose(jvp())/while"),
         ("fused_qlhs_matmul.3", 150, 200, GEMM),
         ("fusion.7", 400, 250, Q)],
        {"gemm": 200, "quant": 250, "attn": 0, "other": 150}, 150),
    "while_in_while": (
        [("while.269", 0, 900, ""),
         ("while.270", 100, 500, Q),
         ("fusion.1", 150, 100, Q),
         ("fusion.2", 300, 200, ATTN),
         ("q8_matmul.1", 700, 100, GEMM)],
        {"gemm": 100, "quant": 100, "attn": 200, "other": 500}, 500),
    "no_containers": (
        [("fusion.1", 0, 300, ATTN), ("fusion.2", 300, 100, ""),
         ("copy.4", 500, 100, "")],
        {"gemm": 0, "quant": 0, "attn": 300, "other": 200}, 0),
    "op_text_as_name": (
        [("%while.5 = (s32[]) while(%t), body=%b", 0, 500, ""),
         ("%fused_qboth_tn_matmul.2 = f32[8,4]{1,0} custom-call(%a)", 100,
          300, GEMM)],
        {"gemm": 300, "quant": 0, "attn": 0, "other": 200}, 200),
}


@pytest.mark.parametrize("case", sorted(NESTED))
def test_self_time_goes_to_the_innermost_op(case):
    ops, want, own = NESTED[case]
    r = _reduce(ops)
    att = attribution.attribute(r)
    for cat in attribution.CATEGORIES:
        assert att["seconds"][cat] == pytest.approx(want[cat] * 1e-9,
                                                    abs=1e-15), cat
    # every busy nanosecond lands on exactly one category
    assert att["self_s"] == pytest.approx(r["busy_s"])
    rows = dict((n, s) for n, s in att["other_top"])
    assert rows[attribution.CONTAINER_ROW] == pytest.approx(own * 1e-9,
                                                            abs=1e-15)
    # the reduction itself still counts every event at full length
    assert r["ops"][ops[0][0]]["seconds"] == pytest.approx(ops[0][2] * 1e-9)


def test_kernel_calls_and_self_time_by_exact_name():
    r = _reduce([("q8_matmul.1", 0, 100, GEMM), ("q8_matmul.2", 100, 100, ""),
                 ("q8_matmul.1", 300, 100, GEMM),
                 ("q8_matmul_twin.1", 400, 100, GEMM),
                 ("quantize_sr_rows", 500, 50, Q)])
    att = attribution.attribute(r)
    assert att["kernel_calls"] == {"q8_matmul": 3, "quantize_sr_rows": 1}
    assert att["kernel_s"]["q8_matmul"] == pytest.approx(300e-9)
    # a name that only starts like a kernel's is no kernel
    assert att["seconds"]["gemm"] == pytest.approx(300e-9)
    assert att["seconds"]["quant"] == pytest.approx(150e-9)


@pytest.mark.parametrize("op,scope,cat", [
    ("fused_qlhs_matmul.4", Q, "gemm"),          # the kernel's name first
    ("fusion.9", Q, "quant"),
    ("fusion.9", "jit(f)/qk[layers.attn.wq]/xor", "quant"),
    ("fusion.9", "jit(f)/q[a|fwd]/x;fp[attn.sdpa]/y", "quant"),
    ("fusion.9", ATTN, "attn"),
    ("fusion.9", "jit(f)/qfp[lm_head|fwd]/dot_general", "other"),
    ("fusion.9", "jit(f)/fp[rwkv.wkv]/mul", "other"),
    ("quantize_sr_rows.1", "", "other"),
    ("fusion.9", "", "other"),
])
def test_first_matching_category_wins(op, scope, cat):
    assert attribution.category(op, scope) == cat


def test_overlapping_leaves_give_no_attribution(capsys):
    r = _reduce([("fusion.1", 0, 500, Q), ("fusion.2", 100, 500, Q)])
    assert attribution.attribute(r) is None
    assert "overlap" in capsys.readouterr().err


def test_gemm_names_are_the_program_table_and_the_cost_modules():
    from repro.kernels import names
    assert set(attribution.KERNELS) == set(names.KERNEL_NAMES)
    step = readers.fqt_step_calls({**_TX, "n_layers": 1}, 16, False)
    assert set(step) <= set(attribution.GEMM_KERNELS)
    for k in step:
        assert os.path.isfile(os.path.join(ROOT, "bench", "kernels",
                                           f"{k}.py")), k


_TX = {"n_layers": 6, "d_model": 512, "n_heads": 4, "n_kv_heads": 4,
       "head_dim": 128, "d_ff": 1024, "vocab_size": 10000, "vocab_pad_to": 256,
       "act": "gelu"}
PEAKS = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def _run_with(ops, steps=2, tokens=64):
    conf = {"train": {"engine": {"remat": False}}}
    return {"out": {"trace": _reduce(ops, (0, 10 ** 9)),
                    "traced_steps": steps},
            "model": _TX, "conf": conf, "peaks": PEAKS,
            "traffic": {"batch": 1, "seq": tokens}}


def _gemm_ops(steps, tokens, ns_each):
    """The GEMM kernel events of ``steps`` steps, each ``ns_each`` long."""
    per = readers.fqt_step_calls(_TX, tokens, False)
    ops, t = [], 0
    for k, v in per.items():
        for _ in range(steps * sum(c for _, c in v)):
            ops.append((f"{k}.1", t, ns_each, GEMM))
            t += ns_each
    return ops, t


def test_gemm_roofline_reader_by_hand():
    read = run.load_reader("fqt_gemm_roofline.train")
    ops, t = _gemm_ops(2, 64, 1000)
    ops.append(("fusion.5", t, 500, Q))
    r = _run_with(ops)
    per = readers.fqt_step_calls(_TX, 64, False)
    least = 2 * sum(c * readers.roofline_seconds(k, s, PEAKS)
                    for k, v in per.items() for s, c in v)
    spent = 2 * 3 * 37 * 1000e-9        # 37 calls a step of each kernel
    assert read(r) == pytest.approx(100.0 * least / spent)
    assert r["out"]["gemm_calls"]["measured"] == {k: 74 for k in per}
    # the other shares read the same attribution
    assert run.load_reader("quant_share.train")(r) == pytest.approx(
        100.0 * 500 / (t + 500))
    assert run.load_reader("attn_share.train")(r) == 0.0


def test_gemm_roofline_reader_refuses_a_call_count_mismatch(capsys):
    ops, _ = _gemm_ops(2, 64, 1000)
    r = _run_with(ops[1:])
    assert run.load_reader("fqt_gemm_roofline.train")(r) is None
    calls = r["out"]["gemm_calls"]
    assert calls["measured"]["fused_qlhs_matmul"] == 73
    assert calls["expected"]["fused_qlhs_matmul"] == 74
    assert "measured" in capsys.readouterr().err


HLO = """HloModule jit_step_fn, entry_computation_layout={()->f32[4]}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step_fn)/q[layers.mlp.fc1|agrad]/vmap()/mul"}
  ROOT %scatter.2 = f32[4]{0} scatter(%mul.1, %param_0)
}

%fused_computation.2 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %fusion.9 = f32[4]{0} fusion(%param_0), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main.3 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.118 = f32[4]{0} fusion(%x), kind=kCustom, calls=%fused_computation.2
  %exp.4 = f32[4]{0} exponential(%x), metadata={op_name="jit(step_fn)/fp[attn.sdpa]/exp" stack_frame_id=3}
  ROOT %add.5 = f32[4]{0} add(%fusion.118, %exp.4), metadata={op_name="jit(step_fn)/add"}
}
"""


def test_scopes_from_the_compiled_text():
    """An instruction's scope is its op_name; a fusion whose own op_name
    carries no marker takes the marked op_names fused into it, however
    deep."""
    names = attribution.op_names(HLO)
    assert names["exp.4"] == "jit(step_fn)/fp[attn.sdpa]/exp"
    assert names["add.5"] == "jit(step_fn)/add"
    assert names["fusion.118"] == (
        ";jit(step_fn)/q[layers.mlp.fc1|agrad]/vmap()/mul")
    assert attribution.category("fusion.118", names["fusion.118"]) == "quant"


def test_shares_take_the_compiled_scopes_where_the_trace_has_none(
        monkeypatch):
    monkeypatch.setattr(attribution, "compiled_step", lambda run: HLO)
    r = _run_with([("%fusion.118 = f32[4]{0} fusion(f32[4]{0} %x)", 0, 300,
                    ""), ("%exp.4 = f32[4]{0} exponential(%x)", 300, 100, ""),
                   ("%add.5 = f32[4]{0} add(%a, %b)", 400, 100, "")])
    assert run.load_reader("quant_share.train")(r) == pytest.approx(60.0)
    assert run.load_reader("attn_share.train")(r) == pytest.approx(20.0)
    assert r["out"]["attribution"]["scopes_from"].startswith("compiled step")


def test_shares_need_a_program_marker_in_the_trace(monkeypatch):
    monkeypatch.setattr(attribution, "compiled_step",
                        lambda run: HLO.replace("[", "").replace("]", ""))
    r = _run_with([("fusion.1", 0, 100, ""), ("fusion.2", 100, 100,
                                              "jit(f)/add")])
    assert run.load_reader("quant_share.train")(r) is None
    assert run.load_reader("attn_share.train")(r) is None
    assert r["out"]["attribution"]["seconds"]["other"] == pytest.approx(
        200e-9)


NEW = ["quant_share.train", "attn_share.train", "fqt_gemm_roofline.train"]


def test_traced_cpu_run_leaves_the_new_metrics_out(tx_tiny):
    """On the CPU the trace has no device plane: the readers find nothing,
    return None and raise nothing."""
    bench = dict(TEST_BENCH, per_layer=TEST_BENCH["per_layer"] + [
        {"name": n, "unit": "%", "better": "lower", "source": "device_trace",
         "workloads": ["tx.train.bhq5"], "moves": "train_tokens_per_s"}
        for n in NEW])
    res = run.run_cell("tx.train.bhq5", 2 ** 31 + 7, 0.2, True,
                       require_chip=False, bench=bench,
                       overrides={"conf": tx_tiny, "traffic": TRAIN_TRAFFIC})
    assert res["_out"]["attribution"] is None
    assert not set(NEW) & set(res["metrics"])


# A chip trace of the cell, ``bench/run.py --workload tx.train.bhq5 --trace 1
# --keep-trace`` on a TPU v5 lite: one chunk of 3 steps, and the text of the
# step it ran, compiled by the same program.
TESTDATA = os.path.join(ROOT, "bench", "testdata")
GEMM3 = ("fused_qlhs_matmul", "fused_qboth_tn_matmul", "q8_matmul")


@pytest.fixture(scope="module")
def chip_trace():
    import gzip

    from jax.profiler import ProfileData
    with gzip.open(os.path.join(TESTDATA, "tx.train.bhq5.xplane.pb.gz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with gzip.open(os.path.join(TESTDATA, "tx.train.bhq5.step_hlo.txt.gz"),
                   "rt") as f:
        hlo = f.read()
    return pd, trace.reduce_profile(pd), hlo


def _innermost_self_time(pd, names):
    """{category: seconds} of the exact self time: every interval of the
    ``XLA Ops`` line goes to the innermost event that covers it."""
    window = next(ev for p in pd.planes if p.name.startswith("/host:")
                  for ln in p.lines for ev in ln.events
                  if ev.name == trace.WINDOW)
    w0, w1 = window.start_ns, window.start_ns + window.duration_ns
    events = sorted(
        (max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1), ev.name)
        for p in pd.planes if p.name.startswith(trace.DEVICE_PREFIX)
        for ln in p.lines if ln.name == trace.OPS_LINE for ev in ln.events
        if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0)
    events.sort(key=lambda e: (e[0], -e[1]))
    own = [e - s for s, e, _ in events]
    stack = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            assert e <= events[stack[-1]][1], "events overlap without nesting"
            own[stack[-1]] -= e - s
        stack.append(i)
    out = dict.fromkeys(attribution.CATEGORIES, 0.0)
    for (_, _, name), ns in zip(events, own):
        scope = names.get(attribution.hlo_name(name), "")
        out[attribution.category(name, scope)] += ns * 1e-9
    return out


def test_chip_trace_reduces_to_fixed_numbers(chip_trace):
    pd, r, hlo = chip_trace
    names = attribution.op_names(hlo)
    att = attribution.attribute(r, names)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(1.005225068)
    assert r["window_s"] == pytest.approx(1.01466949)
    # every busy second on exactly one category
    assert sum(att["seconds"].values()) == pytest.approx(r["busy_s"],
                                                         rel=0.01)
    exact = _innermost_self_time(pd, names)
    for cat in attribution.CATEGORIES:
        assert abs(att["seconds"][cat] - exact[cat]) <= 0.01 * r["busy_s"]
    shares = {c: round(100 * s / r["busy_s"], 1)
              for c, s in att["seconds"].items()}
    assert shares == {"gemm": 9.2, "quant": 72.7, "attn": 4.7, "other": 13.4}
    # 21 kernel instructions by name: 7 quantized sites (6 in the layer
    # scan, the head) x 3 GEMMs; the scan runs its 6 layers, so each GEMM
    # kernel is called 6 x 6 + 1 = 37 times a step, in 3 traced steps
    kernels = {attribution.base_name(n) for n in r["ops"]}
    assert sum(1 for n in r["ops"] if attribution.base_name(n) in GEMM3) == 21
    assert kernels & set(attribution.KERNELS) == set(GEMM3)
    assert att["kernel_calls"] == {k: 3 * 37 for k in GEMM3}
    assert att["seconds"]["quant"] > 0 and att["seconds"]["attn"] > 0


def test_chip_trace_through_the_readers(chip_trace, monkeypatch):
    """The readers on the chip trace, with the step's text standing in for
    the compile after the window."""
    import json
    _, r, hlo = chip_trace
    monkeypatch.setattr(attribution, "compiled_step", lambda run: hlo)
    conf = json.load(open(os.path.join(ROOT, "bench", "configs",
                                       "statquant-tx.json")))
    peaks = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))
    runrec = {"out": {"trace": r, "traced_steps": 3}, "conf": conf,
              "model": conf["model"], "peaks": peaks["TPU v5 lite"],
              "traffic": {"batch": 32, "seq": 512}}
    values = {m: run.load_reader(m)(runrec) for m in NEW}
    assert values == {"quant_share.train": pytest.approx(72.708, abs=1e-3),
                      "attn_share.train": pytest.approx(4.734, abs=1e-3),
                      "fqt_gemm_roofline.train": pytest.approx(45.173,
                                                               abs=1e-3)}
    assert runrec["out"]["attribution"]["scopes_from"].startswith(
        "compiled step")
