"""Benchmark harness (benchmarks/run.py): the machinery must run
end-to-end and emit the schema the baseline record needs. Heavy configs
are TPU-targeted; the CPU-runnable one exercises the whole path."""

import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import run as bench_run  # noqa: E402


def test_config_inventory_matches_baseline():
    """One harness config per BASELINE.json entry, plus the real-text
    byte-LM extension (bytes_lm_real — BASELINE config 3's real-corpus
    analogue)."""
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        n_baseline = len(json.load(f)["configs"])
    assert n_baseline == 5
    extensions = {"bytes_lm_real"}
    assert extensions <= set(bench_run.CONFIGS)
    assert len(set(bench_run.CONFIGS) - extensions) == n_baseline


def test_mlp_cpu_end_to_end():
    res = bench_run.run_config("mlp_cpu", steps=4, warmup=1,
                               full_size=False)
    assert res["config"] == "mlp_cpu"
    assert res["num_devices"] >= 1
    assert res["step_time_ms"] > 0
    assert res["samples_per_sec_per_chip"] > 0
    assert len(res["loss_curve"]) == 4
    assert all(x > 0 for x in res["loss_curve"])
    assert "mfu" in res


def test_cli_writes_out_file(tmp_path):
    out = tmp_path / "res.json"
    rc = bench_run.main(["--config", "mlp_cpu", "--steps", "2",
                         "--warmup", "1", "--out", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["config"] == "mlp_cpu"


@pytest.mark.parametrize("name", sorted(bench_run.CONFIGS))
def test_models_construct(name):
    """Every benchmark config's model builds (scaled size) — catches
    registry/kwargs drift without training."""
    from distributed_training_tpu.models import build_model
    spec = bench_run.CONFIGS[name]
    model_name, kwargs = spec["model"]
    kwargs = dict(kwargs)
    kwargs.update(spec.get("scaled_kwargs", {}))
    model = build_model(model_name, dtype="float32", **kwargs)
    assert model is not None


def test_is_oom_classification():
    """_is_oom (the 1B ladder's fall-through predicate) matches real
    device-OOM signatures and nothing else — a bare "allocat" substring
    would reroute deterministic failures down the ladder."""
    from bench_1b_single_chip import _is_oom

    assert _is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "1207959552 bytes"))
    assert _is_oom(RuntimeError("ran out of memory on device"))
    assert _is_oom(RuntimeError("Failed to allocate request"))
    # NOT OOM: mentions allocation but is a different failure class
    assert not _is_oom(RuntimeError(
        "could not allocate a tracer: shape mismatch"))
    assert not _is_oom(TypeError("bad shapes"))


def test_bench_1b_measurement_path_cpu(cpu8):
    """The exact 1B single-chip measurement path (adafactor + full
    remat + bf16 through the real Trainer) at toy scale — catches
    config drift in the script before a scarce healthy-chip window
    burns on it."""
    import bench_1b_single_chip as b1

    del cpu8  # fixture pins the 8-device CPU platform
    rec = b1.run(seq_len=16, optimizer="adafactor", offload=False,
                 model_name="transformer",
                 model_kwargs=dict(vocab_size=64, d_model=32,
                                   n_layers=2, n_heads=4,
                                   max_seq_len=16,
                                   attention_impl="naive"),
                 vocab_size=64)
    import math
    assert rec["metric"] == "transformer_1b_train_single_chip"
    assert rec["tokens_per_sec_per_chip"] > 0
    assert rec["optimizer"] == "adafactor"
    assert math.isfinite(rec["loss"])


def test_analyze_trace_category_classifier():
    """Category rollup labels: the tool's own Category column wins;
    name patterns are the fallback; unknown ops land in 'other'."""
    import analyze_trace as at

    assert at.op_category({"Category": "Fusion"}) == "fusion"
    assert at.op_category(
        {"Operation Name": "dot_general.42"}) == "matmul"
    # Collectives win over their gather/scatter substrings — the
    # misattribution that would invert a matmul-vs-comms conclusion.
    assert at.op_category(
        {"Operation Name": "all-reduce.3"}) == "collective"
    assert at.op_category(
        {"Operation Name": "all-gather.5"}) == "collective"
    assert at.op_category(
        {"Operation Name": "reduce-scatter.1"}) == "collective"
    assert at.op_category(
        {"Operation Name": "all-to-all.2"}) == "collective"
    assert at.op_category(
        {"Operation Name": "collective-permute.9"}) == "collective"
    assert at.op_category({"Operation Name": "gather.4"}) == "gather"
    assert at.op_category({"Operation Name": "copy.7"}) == "copy"
    assert at.op_category(
        {"Operation Name": "mysterious.1"}) == "other"
    assert at.op_category({}) == "other"


def test_fsdp_tpu_pipeline_grad_sync_is_reduce_scatter():
    """VERDICT r4 item 4, resolved with compiled evidence: on the REAL
    TPU compiler (device-less topology AOT via libtpu — no chip
    needed), the FSDP gradient sync lowers to fused reduce-scatter
    kernels (kCustom %all-reduce-scatter fusions), NOT the
    all-reduce + slice the CPU partitioner shows. Root cause of the
    r4 "2x optimal traffic" worry was twofold: (a) the audit parser
    double-counted the fusion's INNER all-reduce at full pre-scatter
    bytes, and (b) tie_embeddings=True forces the one genuinely-full
    all-reduce (the tied weight's gradient merges an embedding-layout
    and a head-layout contribution). The scale presets that FSDP
    exists for (transformer_1b/_7b) are untied — pinned here: untied
    FSDP has reduce-scatter rows and NO param-scale all-reduce.
    Remaining all-reduces are replicated-param grads (norm scales,
    biases, pos-embed) — correct and small."""
    import audit_collectives as ac

    try:
        from distributed_training_tpu.runtime import topology_runtime
        topology_runtime(4, "v5e:2x2")
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    text = ac.compile_step_hlo(4, "fsdp", {"fsdp": 4},
                               {"tie_embeddings": False},
                               tpu_topology="v5e:2x2")
    rep = ac.audit_hlo_text(text)
    rs = rep["by_kind"].get("reduce-scatter", {"count": 0})
    assert rs["count"] >= 1, rep["by_kind"]
    big_ars = [r for r in rep["rows"] if r["kind"] == "all-reduce"
               and len(r["shape"].split(",")) >= 2
               and all(int(d) >= 64 for d in r["shape"].split(","))]
    assert not big_ars, big_ars

    # And the DDP contract on the same real pipeline: gradient
    # all-reduces are the ONLY collective kind in a DDP step.
    text = ac.compile_step_hlo(4, "ddp", {"dp": 4},
                               tpu_topology="v5e:2x2")
    rep = ac.audit_hlo_text(text)
    assert set(rep["by_kind"]) == {"all-reduce"}, rep["by_kind"]


def test_multidevice_flash_compiles_under_tpu_compiler(monkeypatch):
    """Regression pin for a bug only the real TPU pipeline can see:
    the SPMD partitioner cannot partition Mosaic custom calls, so the
    plain-jit flash path that works single-chip FAILED to compile on
    any multi-device mesh ('Mosaic kernels cannot be automatically
    partitioned') — masked on CPU dryruns, where dispatch demotes to
    naive. The model now wraps per-shard flash in shard_map over the
    data (and tp head) axes; this compiles the audit model on fsdp=4
    with the kernels ACTIVE (DTT_ASSUME_TPU=1) and asserts Pallas
    calls are present in the partitioned program."""
    import audit_collectives as ac

    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    try:
        from distributed_training_tpu.runtime import topology_runtime
        topology_runtime(4, "v5e:2x2")
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")
    # S=256 so the flash kernels are tile-eligible (supported() wants
    # S >= 128); the audit default S=32 would demote to naive and
    # prove nothing.
    text = ac.compile_step_hlo(
        4, "fsdp", {"fsdp": 4},
        {"max_seq_len": 256, "tie_embeddings": False},
        tpu_topology="v5e:2x2", seq_len=256)
    assert 'custom_call_target="tpu_custom_call"' in text


def test_headline_kernels_compile_under_tpu_compiler(monkeypatch):
    """The Pallas flash kernels (seq-aware 1024x1024 tiles, fused
    single-sweep backward) must compile under the REAL TPU compiler —
    Mosaic's VMEM check is the ground truth the estimator in
    _fused_bwd_fits approximates. Device-less topology AOT with
    DTT_ASSUME_TPU=1 (without it, trace-time platform detection sees
    the host CPU and 0 Pallas kernels reach the compiled HLO — this
    test also pins that the override works). Expect exactly 2
    tpu_custom_calls: the forward kernel in the layer scan + the fused
    backward in the remat region, mirroring the jaxpr-level pin in
    test_remat_policies_do_not_recompute_flash_kernel."""
    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    import precompile_points as pp
    try:
        from distributed_training_tpu.runtime import topology_runtime
        topology_runtime(1, "v5e:2x2")
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")
    rec = pp.compile_point("test_b8", 8, 1024, "gpt2_125m",
                           dict(remat=True, remat_policy="mlp"))
    assert rec["ok"], rec
    assert rec["pallas_calls"] == 2, rec
    assert rec["temp_gib"] < 14, rec


@pytest.mark.parametrize("split", [False, True],
                         ids=["fused_bwd", "split_bwd"])
def test_flash_kernels_keep_their_names_under_tpu_compiler(
        monkeypatch, split):
    """The TPU compiler names a Pallas custom call after the innermost
    name-stack entry above it, so an unnamed kernel is called after
    whichever transform wraps it (``checkpoint.10``, ``closed_call.7``,
    ``shard_map.223``: ledger, PR 24) and no reader of the device trace
    can be written against it. With ``name=`` on the
    ``pl.pallas_call``s the instruction is ``dtt_flash_*.N`` under
    ``jax.checkpoint`` + ``shard_map`` + the custom VJP alike, and what
    ``perfbench/trace_reduce.py::short_name`` keeps of it is what
    ``ops.flash_time_share.train`` looks for."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_training_tpu.ops import flash_attention as fa
    from perfbench import common, trace_reduce

    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    monkeypatch.setattr(fa, "_FORCE_SPLIT_BWD", split)
    try:
        from distributed_training_tpu.runtime import topology_runtime
        mesh = topology_runtime(4, "v5e:2x2").mesh
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    def loss(q, k, v):
        attend = jax.shard_map(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
            mesh=mesh, in_specs=(P("dp"),) * 3, out_specs=P("dp"),
            check_vma=False)
        return jax.checkpoint(attend)(q, k, v).astype(
            jnp.float32).sum()

    x = jax.ShapeDtypeStruct((4, 256, 2, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if " custom-call(" in line
             and 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(re.match(r"%([\w.]+) = ", c).group(1).rsplit(
        ".", 1)[0] for c in calls)
    assert names == (["dtt_flash_bwd_dkv", "dtt_flash_bwd_dq",
                      "dtt_flash_fwd"] if split
                     else ["dtt_flash_bwd_fused", "dtt_flash_fwd"])
    pattern = common.load_file(
        "layer_metrics", "ops.flash_time_share.train").PATTERN
    for c in calls:
        assert pattern.match(trace_reduce.short_name(c)), c


@pytest.mark.parametrize("S", [1, 4], ids=["resident", "spec4"])
def test_decode_shaped_paged_attention_reads_pool_in_place(S):
    """The decode-shaped call of ``gpt2xl.serve_decode`` (16 slots x
    ``S`` queries, 25 heads of 64, a 385-page pool, bfloat16) in the
    pool form compiles
    for a v5e to a program that gathers nothing and holds no float32
    array the size of the gathered block. The gather form compiled to
    ``f32[1024,25,16,64]`` converts and multiply-reduces that held 74%
    of the cell's device time (``convert.66``, ledger, PR 25); a
    refactor that brings them back fails here, not on the chip."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import PoolLayout

    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    B, H, hd, P, N, ps = 16, 25, 64, 64, 385, 16

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    layout = PoolLayout(H, hd)
    pool = layout.layer(shape(layout.shape(1, N, ps), jnp.bfloat16), 0)
    # The form a pool with sharded heads takes at these shapes; on one
    # chip the rule now hands them to the ragged form's kernel (PR 37).
    assert pa.chunk_form((B, S, H, hd), (H, N, ps, hd), (B, P), 2,
                         ragged=False) == "pool"
    text = jax.jit(pa._pool_attention).lower(
        shape((B, S, H, hd), jnp.bfloat16), pool, pool,
        shape((B, P), jnp.int32),
        shape((B, S), jnp.int32)).compile().as_text()
    assert " gather(" not in text
    gathered = B * P * ps * H * hd
    largest = max(math.prod(int(d) for d in dims.split(","))
                  for dims in re.findall(r"f32\[([0-9,]+)\]", text))
    assert largest < gathered / 2, (largest, gathered)
    assert text.count(" convolution(") == 2     # both dots on the MXU


@pytest.mark.parametrize("shape,heads,P,N,window,form", [
    # smallthinker-21b-ep4's prompt chunk over a window layer's ring
    # and over a global layer's table ...
    ((1, 1024, 28, 128), 4, 320, 10241, 4096, "flash.window"),
    ((1, 1024, 28, 128), 4, 1024, 32769, None, "flash"),
    # ... and gpt2-xl's prefill program and a verify chunk of 8, which
    # the rule leaves to XLA.
    ((4, 128, 25, 64), 25, 64, 385, None, "gather"),
    ((16, 8, 25, 64), 25, 64, 385, None, "pool"),
], ids=["ring_1x1024", "table_1x1024", "xl_4x128", "xl_16x8"])
def test_prompt_chunk_attention_is_one_kernel_and_holds_no_logits(
        monkeypatch, shape, heads, P, N, window, form):
    """``paged_attention_chunk`` at ``smallthinker_ep4.serve_long``'s
    two prefill shapes (bfloat16 pools at the cell's sizes) compiles
    for a v5e, under Mosaic's VMEM check, to a program with the custom
    call ``dtt_paged_prefill``, no ``reduce-window`` (the compiler had
    turned the row maximum of ``f32[4,256,7,5120]`` into a window of
    10,239 at every logit: three fusions, 40% of the cell's device
    time, ledger, PR 32) and no float32 array as large as the logits of
    one head-block; at ``gpt2-xl``'s shapes it holds no such call. The
    name on the instruction is what ``ops.paged_prefill_time_share.
    decode`` looks for."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import PoolLayout
    from perfbench import common, trace_reduce

    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    def struct(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    B, S, H, hd = shape
    layout = PoolLayout(heads, hd)
    pool = layout.layer(struct(layout.shape(1, N, 16), jnp.bfloat16), 0)
    with pa.observe_forms() as seen:
        text = jax.jit(lambda *a: pa.paged_attention_chunk(
            *a, window=window, ring=bool(window))).lower(
            struct(shape, jnp.bfloat16), pool, pool,
            struct((B, P), jnp.int32),
            struct((B, S), jnp.int32)).compile().as_text()
    assert seen == [form]
    calls = [line.strip() for line in text.splitlines()
             if " custom-call(" in line
             and 'custom_call_target="tpu_custom_call"' in line]
    if "flash" not in form:
        assert not calls
        return
    assert len(calls) == 1
    assert re.match(r"%dtt_paged_prefill\.\d+ = ", calls[0])
    pattern = common.load_file(
        "layer_metrics", "ops.paged_prefill_time_share.decode").PATTERN
    assert pattern.match(trace_reduce.short_name(calls[0])), calls[0]
    assert "reduce-window" not in text
    largest = max([math.prod(int(d) for d in dims.split(","))
                   for dims in re.findall(r"f32\[([0-9,]+)\]", text)]
                  or [0])
    assert largest < S * H * P * 16, largest


@pytest.mark.parametrize("shape,heads,P,N,layers,window", [
    # smallthinker-21b-ep4's resident decode over a global layer's
    # table and a window layer's ring, the carried pools whole ...
    ((32, 1, 28, 128), 4, 1024, 32769, 3, None),
    ((32, 1, 28, 128), 4, 320, 10241, 9, 4096),
    # ... and gpt2-xl's, resident decode and speculative verify.
    ((16, 1, 25, 64), 25, 64, 385, 48, None),
    ((16, 4, 25, 64), 25, 64, 385, 48, None),
], ids=["thinker_table_32x1", "thinker_ring_32x1", "xl_16x1", "xl_16x4"])
def test_decode_attention_is_one_kernel_over_the_carried_pool(
        monkeypatch, shape, heads, P, N, layers, window):
    """The ragged form at the resident decode shapes of
    ``smallthinker_ep4.serve_long`` and ``gpt2xl.serve_decode`` (bfloat16
    pools at the cells' sizes, every layer of them) compiles for a v5e,
    under Mosaic's VMEM and SMEM checks, to a program whose one custom
    call ``dtt_paged_decode`` takes the WHOLE pools as operands: no
    gather, no slice or copy of a layer (the six ``bf16[32768,16,512]``
    gathers, six ``bf16[10240,16,512]`` and twelve
    ``bf16[32,16384,4,128]`` copies that were 70.8% of the first cell's
    device time: ledger, PR 36), no ``reduce-window``, no float32 array
    an eighth the size of the gathered block (what is there is the
    queries' spread), no temporaries to speak of. The name
    on the instruction is what ``ops.paged_decode_time_share.decode``
    looks for."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import PoolLayout
    from perfbench import common, trace_reduce

    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    def struct(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    B, S, H, hd = shape
    layout = PoolLayout(heads, hd)
    pool = layout.layer(struct(layout.shape(layers, N, 16), jnp.bfloat16),
                        struct((), jnp.int32))
    compiled = jax.jit(lambda *a: pa._ragged_attention(
        *a, window=window, ring=bool(window))).lower(
        struct(shape, jnp.bfloat16), pool, pool,
        struct((B, P), jnp.int32), struct((B, S), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line.strip() for line in text.splitlines()
             if " custom-call(" in line
             and 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert re.match(r"%dtt_paged_decode\.\d+ = ", calls[0])
    pattern = common.load_file(
        "layer_metrics", "ops.paged_decode_time_share.decode").PATTERN
    assert pattern.match(trace_reduce.short_name(calls[0])), calls[0]
    # Both pools go in whole, as the program's own parameters.
    whole = "bf16[%s]" % ",".join(map(str, pool.pool.shape))
    assert calls[0].count(whole) == 2, calls[0]
    assert " gather(" not in text and "reduce-window" not in text
    assert not re.search(r"bf16\[[0-9,]*,16,%d\]\S* (copy|dynamic-slice)"
                         % layout.lanes, text)
    largest = max([math.prod(int(d) for d in dims.split(","))
                   for dims in re.findall(r"f32\[([0-9,]+)\]", text)]
                  or [0])
    assert largest < B * P * 16 * heads * hd / 8, largest
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("B,S,form", [
    (1, 1024, "flash.sparse"), (32, 1, "absorbed.sparse"),
], ids=["prefill_1x1024", "decode_32x1"])
def test_a_chunk_under_the_selection_is_one_kernel_and_gathers_no_row(
        monkeypatch, B, S, form):
    """``latent_attention_chunk(select=)`` at ``dots3_ep8.serve_sparse``'s
    two shapes (a full layer at the published widths, bfloat16 pools, a
    table of 16,384 rows, top 2,048) compiles for a v5e: the prompt
    chunk, under Mosaic's VMEM check, to a program with the one attention
    call ``dtt_sparse_prefill``, the name ``ops.sparse_prefill_time_
    share.decode`` looks for, and no array of the 262,144 gathered rows
    a block of 128 queries read one at a time (1.48 s of the cell's 4.0
    s trace: ledger, PR 34); the decode iteration to the gather form
    and no such call. In both the selection is found by counting, one
    ``dtt_select_topk`` and no sort (sorts were a quarter of the cell's
    device time)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.ops import paged_attention as pa
    from distributed_training_tpu.serving.kv_cache import PoolLayout
    from perfbench import common, trace_reduce

    monkeypatch.setenv("DTT_ASSUME_TPU", "1")
    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    bf, H, P, topk = jnp.bfloat16, 128, 1024, 2048

    def struct(dims, dtype=bf):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def layer(width):
        layout = PoolLayout(1, width)
        return layout.layer(struct(layout.shape(1, B * P + 1, 16)), 0)

    def call(qn, qr, c, r, t, qp, uk, uv, iq, iw, ip):
        return pa.latent_attention_chunk(
            qn, qr, c, r, t, qp, uk, uv,
            select=pa.Selection(iq, iw, ip, topk))

    with pa.observe_forms() as seen:
        text = jax.jit(call).lower(
            struct((B, S, H, 128)), struct((B, S, H, 64)), layer(512),
            layer(64), struct((B, P), jnp.int32),
            struct((B, S), jnp.int32), struct((512, H, 128)),
            struct((512, H, 128)), struct((B, S, 64, 128)),
            struct((B, S, 64), jnp.float32), layer(128)
        ).compile().as_text()
    assert seen == [form, "select.threshold"]
    calls = [line.strip() for line in text.splitlines()
             if " custom-call(" in line
             and 'custom_call_target="tpu_custom_call"' in line]
    # The exact top-k by counting, in its own kernel, and no sort.
    selects = [c for c in calls if re.match(r"%dtt_select_topk\.\d+ = ", c)]
    assert len(selects) == 1
    assert not re.search(r"= \S+ sort\(", text)
    calls = [c for c in calls if c not in selects]
    gathered = re.findall(r"bf16\[(?:128,2048|262144),(?:1,)?512\]", text)
    if form == "absorbed.sparse":
        assert not calls
        return
    assert len(calls) == 1
    assert re.match(r"%dtt_sparse_prefill\.\d+ = ", calls[0])
    pattern = common.load_file(
        "layer_metrics", "ops.sparse_prefill_time_share.decode").PATTERN
    assert pattern.match(trace_reduce.short_name(calls[0])), calls[0]
    assert not gathered, gathered[:3]


def test_the_latent_form_tables_sparse_rows_rehearse():
    """``benchmarks/latent_form_table.py::sparse_rows`` (the table
    ``sparse_form``'s one constant was read from: a full layer's call
    under its selection in its masked and its gather form, context by
    context) at a tiny size on the CPU, bfloat16 as there: both forms
    are timed, agree within ``chip_smoke.py``'s band, and the rule is
    reported."""
    sys.path.insert(0, REPO)             # chip_smoke.py, at the root
    import latent_form_table

    rows = latent_form_table.sparse_rows(
        4, (40, 60), B=2, S=16, H=4, rank=128, nope=8, rope=4, v=8,
        index=(2, 8, 8))
    assert [r["context"] for r in rows] == [40, 60]
    for row in rows:
        assert row["table"] == 64 and row["rule"] == "flash"
        assert row["flash_ms"] > 0 and row["absorbed_ms"] > 0
        assert row["err_over_bf16_band"] < 1.0
        assert row["faster"] in ("flash", "absorbed")


def test_the_select_form_table_rehearses(monkeypatch):
    """``benchmarks/select_form_table.py`` (the selection's forms at a
    prompt chunk's query block and a resident iteration, context by
    context) at a tiny size on the CPU: every form and width is timed
    and chooses the set the sort chooses, the kernel's rows past their
    seen positions included."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import select_form_table as table

    for name, value in dict(SLOTS=512, TOPK=64, HEADS=4, DIM=8,
                            INPUTS=2).items():
        monkeypatch.setattr(table, name, value)
    rows = table.table(1, contexts=(100, 512))
    assert [(r["S"], r["context"]) for r in rows] == [
        (128, 100), (128, 512), (1, 100), (1, 512)]
    for row in rows:
        assert row["same_set"], row
        assert row["sort_ms"] > 0 and row["threshold_ms"] > 0
        if row["S"] > 1:
            assert row["xla_ms"] > 0 and row["threshold_lanes256_ms"] > 0


def test_resident_decode_holds_no_copy_of_the_pool():
    """``jit_serving_resident_decode`` at ``gpt2-xl``'s widths (25
    heads of 64, 16 slots, 385 pages, bfloat16; 4 layers of 48)
    compiles for a v5e with no ``copy`` whose shape is the whole pool
    and with temporaries under half the pool's bytes. Stored ``(L,
    Hkv, N, ps, 64)`` every program held the pool again re-laid-out,
    heads padded 25 -> 32 and 64 lanes -> 128: 2.56 times its bytes
    in temporaries (``device.reserved_hbm_gb.decode`` 4.85: ledger,
    PR 28) and four whole-pool copies an iteration. A layout that
    brings them back fails here, not on the chip. The entry
    parameters are given the default layouts the engine's arrays
    have: left free, the compiler picks others and the count
    misleads."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.serving import engine as E
    from distributed_training_tpu.serving.kv_cache import (
        PagedCacheConfig, PagedKVCache)

    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=Format(
            Layout(major_to_minor=tuple(range(len(dims)))), chip))

    model = build_model(
        "gpt2", dtype="bfloat16", vocab_size=50304, d_model=1600,
        n_layers=4, n_heads=25, max_seq_len=1024,
        pos_encoding="learned", tie_embeddings=True)
    ecfg = E.EngineConfig(
        max_batch=16, num_pages=385, page_size=16, max_seq_len=1024,
        prefill_chunk=128, resident_k=8, prefill_slots=4)
    block = model.serving_block()
    pools = [shape(dims, jnp.bfloat16)
             for dims in PagedKVCache.pool_shapes(PagedCacheConfig(
                 **block.cache, page_size=16, num_pages=385,
                 max_seq_len=1024))]
    params = jax.tree.map(
        lambda a: shape(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    B, P = 16, 64
    carried = [shape((1, B, 1024), jnp.int32), shape((1, B), jnp.int32),
               shape((1, B), jnp.int32)]
    compiled = E.build_resident_decode_fn(block, ecfg).lower(
        params, *pools, *carried, shape((1, B, P), jnp.int32),
        shape((1, B), jnp.int32), shape((1, B), jnp.bool_)).compile()
    # The carried slot table (history, lengths, tokens left) is donated
    # like the pools: each parameter's buffer holds an output (which
    # of the (1, B) ones is the compiler's pick among equal shapes),
    # the history's its own, and the history is nowhere copied whole.
    text = compiled.as_text()
    first = len(jax.tree.leaves(params))
    aliases = dict(
        (int(p), int(o)) for o, p in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
            re.search(r"input_output_alias=\{(.*?)\}, entry",
                      text).group(1)))
    assert set(aliases) == set(range(first, first + 5)), aliases
    assert (aliases[first], aliases[first + 1], aliases[first + 2]) == (
        7, 8, 4), aliases
    assert not re.search(r"= s32\[1,16,1024\]\S* copy\(", text)
    pool_elems = math.prod(pools[0].shape)
    whole = [line.strip()[:120] for line in text.splitlines()
             if (m := re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([0-9,]+)\]"
                               r"\S* copy\(", line))
             and math.prod(map(int, m.group(1).split(","))) == pool_elems]
    assert not whole, whole
    pool_bytes = sum(2 * math.prod(p.shape) for p in pools)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 2, (temp, pool_bytes)


def test_resident_decode_appends_without_a_whole_row_pass():
    """``jit_serving_resident_decode`` at the serving cells' slot table
    (32 slots of 16,384 positions, ``smallthinker_ep4.serve_long`` and
    ``dots3_ep8.serve_sparse``) on a tiny block compiles for a v5e with
    no ``gather`` and no ``select`` whose result is the whole history,
    and no copy of it. The loop appended an iteration's tokens by
    gathering from them at every position of every row and selecting
    the result into the row: 7 ns an element on the chip, 3.7 ms of a
    14.5 ms iteration (ledger, PR 38). It now scatters B x C values;
    a change that brings the whole-row pass back fails here."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from distributed_training_tpu.models import build_model
    from distributed_training_tpu.serving import engine as E
    from distributed_training_tpu.serving.kv_cache import (
        PagedCacheConfig, PagedKVCache)

    try:
        from distributed_training_tpu.runtime import topology_runtime
        chip = SingleDeviceSharding(
            topology_runtime(1, "v5e:2x2").mesh.devices.flat[0])
    except Exception as e:  # pragma: no cover - no libtpu
        pytest.skip(f"device-less TPU topology unavailable: {e}")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=Format(
            Layout(major_to_minor=tuple(range(len(dims)))), chip))

    B, L, ps, N = 32, 16384, 16, 64
    model = build_model(
        "gpt2", dtype="bfloat16", vocab_size=512, d_model=128,
        n_layers=1, n_heads=2, max_seq_len=L, pos_encoding="learned",
        tie_embeddings=True)
    ecfg = E.EngineConfig(
        max_batch=B, num_pages=N, page_size=ps, max_seq_len=L,
        prefill_chunk=128, resident_k=8, prefill_slots=4)
    block = model.serving_block()
    pools = [shape(dims, jnp.bfloat16)
             for dims in PagedKVCache.pool_shapes(PagedCacheConfig(
                 **block.cache, page_size=ps, num_pages=N,
                 max_seq_len=L))]
    params = jax.tree.map(
        lambda a: shape(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    text = E.build_resident_decode_fn(block, ecfg).lower(
        params, *pools, shape((1, B, L), jnp.int32),
        shape((1, B), jnp.int32), shape((1, B), jnp.int32),
        shape((1, B, L // ps), jnp.int32), shape((1, B), jnp.int32),
        shape((1, B), jnp.bool_)).compile().as_text()
    whole = [f"{op} {name}" for name, dims, op in re.findall(
        r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([0-9,]+)\]\S* "
        r"(gather|select|copy)\(", text, re.M)
        if math.prod(map(int, dims.split(","))) == B * L]
    assert not whole, whole
    assert re.search(r"= s32\[32,16384\]\S* scatter\(", text)


def test_collectives_report_counts_pallas_calls():
    """The `collectives` event says which kernels the compiled step
    runs: ``pallas_calls`` counts Mosaic custom calls in the HLO text
    (chip_smoke.py requires >= 2 of the gpt2_125m step; 0 means the
    naive attention path)."""
    from distributed_training_tpu.telemetry.collectives import (
        audit_hlo_text)

    call = ('  %c = bf16[4] custom-call(%a), '
            'custom_call_target="tpu_custom_call"\n')
    assert audit_hlo_text("ENTRY %main {\n}\n")["pallas_calls"] == 0
    assert audit_hlo_text(
        "ENTRY %main {\n" + call * 3 + "}\n")["pallas_calls"] == 3


def test_audit_matmuls_tiny_model_all_bf16():
    """The offline dot_general audit (benchmarks/audit_matmuls.py) on a
    tiny flash-forced model: every dot in the step is bf16 x bf16 (the
    TPU program's MXU discipline — this is the check that caught the
    flash-backward f32 upcasts), totals are positive, and the naive
    path's known mixed-precision bwd dots are visible when forced."""
    import audit_matmuls

    rep = audit_matmuls.audit(2, 256, {
        "attention_impl": "flash", "n_layers": 2, "d_model": 128,
        "n_heads": 4, "vocab_size": 512, "max_seq_len": 256})
    assert rep["n_dots"] > 0 and rep["total_dot_flops"] > 0
    assert set(rep["flops_by_dtype_pair"]) == {"bfloat16xbfloat16"}
    assert rep["f32_offenders"] == []


def test_profile_step_merges_duplicate_model_kwargs(capsys):
    """--model-kwargs carrying remat/attention_impl must merge with the
    convenience flags, not TypeError (this crashed the r4 trace32
    harvest two seconds into a healthy chip window)."""
    import profile_step

    rc = profile_step.main([
        "--batch", "2", "--seq-len", "128", "--iters", "1",
        "--vocab-size", "256",
        "--model-kwargs",
        '{"remat": true, "remat_policy": "mlp", "n_layers": 2, '
        '"d_model": 64, "n_heads": 2, "max_seq_len": 128, '
        '"vocab_size": 256}'])
    assert rc == 0
    assert "step mfu" in capsys.readouterr().out


def test_ddp_step_collectives_are_grad_allreduce_only():
    """Communication contract (benchmarks/audit_collectives.py): a DDP
    train step's only collectives are gradient all-reduces (plus the
    scalar agreed-stop reduce) — no all-gathers, no all-to-alls.

    Regression pin for a real bug this audit found: the fused xent
    head used to flatten (B, S) into row chunks, merging the
    dp-sharded batch axis into the row axis, and the SPMD partitioner
    responded by ALL-GATHERING the hidden states and tokens across
    data-parallel ranks every step (5 gathers, activation-sized — at
    GPT-2 scale hundreds of MB of interconnect traffic per step that
    the dense head never paid). Sequence-axis chunking keeps the loss
    shard-local."""
    import audit_collectives as ac

    text = ac.compile_step_hlo(8, "ddp")
    rep = ac.audit_hlo_text(text)
    assert rep["by_kind"].get("all-gather", {"count": 0})["count"] == 0, rep
    assert rep["by_kind"].get("all-to-all", {"count": 0})["count"] == 0, rep
    assert rep["by_kind"]["all-reduce"]["count"] >= 1
    # Gradient sync must move roughly the full parameter set once
    # (tiny model ≈ 339 KB of f32 grads), not activation-scale bytes.
    assert rep["by_kind"]["all-reduce"]["bytes"] < 1_000_000

    # FSDP on a real fsdp mesh must gather params (sanity that the
    # audit sees strategy differences, not that it pins FSDP's exact
    # schedule — partitioner choices at toy scale are heuristic).
    text = ac.compile_step_hlo(8, "fsdp", {"fsdp": 8})
    rep = ac.audit_hlo_text(text)
    assert rep["by_kind"].get("all-gather", {"count": 0})["count"] > 0


def test_audit_collectives_async_hlo_counted_once():
    """TPU HLO emits collectives as '-start'/'-done' pairs; the audit
    must count each collective once with the done's (true result)
    bytes — the start's tuple aliases operand+result and would
    roughly triple the byte estimate."""
    import audit_collectives as ac

    text = """
      %ar0 = (f32[100]{0}, f32[100]{0}) all-reduce-start(%x)
      %ar1 = f32[100]{0} all-reduce-done(%ar0)
      %ag = f32[4,8]{1,0} all-gather(%y), dimensions={0}
      %cp0 = (bf16[2,8]{1,0}, bf16[2,8]{1,0}) collective-permute-start(%z)
      %cp1 = bf16[2,8]{1,0} collective-permute-done(%cp0)
    """
    rep = ac.audit_hlo_text(text)
    assert rep["by_kind"]["all-reduce"] == {"count": 1, "bytes": 400}
    assert rep["by_kind"]["all-gather"] == {"count": 1, "bytes": 128}
    assert rep["by_kind"]["collective-permute"] == {
        "count": 1, "bytes": 32}


def test_fsdp_step_has_no_activation_scale_collectives():
    """FSDP compute contract (TrainConfig.fsdp_gather_for_compute):
    weights are all-gathered for their matmuls; ACTIVATIONS never pay
    collective traffic. Without the gather-for-compute constraint the
    partitioner ran partial matmuls on weight shards and all-reduced
    activation-shaped tensors — (B, S, V) logits, (B, S, H, D) qkv —
    dwarfing the parameter traffic (measured: 108 MB -> 9.5 MB per
    step at the audit scale). Activation shapes are recognizable by
    their leading global-batch dim."""
    import audit_collectives as ac

    def activation_rows(rep):
        # Empirically derived against BOTH states of the fix (see the
        # module history): with gather-for-compute bound, every
        # collective is param-shaped — rank <= 2, or rank >= 3 with a
        # leading stacked-layer-slice dim of 1. Monkeypatching the fix
        # off reintroduces 14 activation-shaped rows (rank >= 3,
        # leading dim 128) totalling ~27 MB — exactly what this
        # filter must catch. Scan EVERY row, not the top-10 "largest"
        # slice, so nothing hides below rank 10.
        return [r for r in rep["rows"]
                if len(r["shape"].split(",")) >= 3
                and r["shape"] != "scalar"
                and int(r["shape"].split(",")[0]) >= 16]

    text = ac.compile_step_hlo(8, "fsdp", {"fsdp": 8})
    rep = ac.audit_hlo_text(text)
    assert not activation_rows(rep), activation_rows(rep)
    assert rep["by_kind"].get("all-gather", {"count": 0})["count"] > 0

    # Same contract for a routed-MoE model: expert/router weights are
    # fsdp-sharded too (strategy rules route 'expert' onto fsdp) and
    # flow through the same gather-for-compute constraint; the
    # grouping is batch-preserving (sequence-chunk groups) so routing
    # and dispatch stay shard-local. ZERO activation-scale rows: the
    # r4 remainder (lax.top_k lowering to an unpartitionable TopK
    # custom-call that all-gathered the (B, G, gs, E) routing probs)
    # is gone — routing now selects via _topk_by_argmax, which the
    # partitioner keeps shard-local.
    text = ac.compile_step_hlo(
        8, "fsdp", {"fsdp": 8},
        {"moe_num_experts": 4, "moe_group_size": 64})
    rep = ac.audit_hlo_text(text)
    assert not activation_rows(rep), activation_rows(rep)
