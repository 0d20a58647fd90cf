"""Unit tests for the HLO cost walker (launch/roofline.py) against
hand-checkable compiled programs."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.roofline import analyze_hlo_text, pod_crossing_bytes
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))

    # 1. trip-count awareness: L scanned matmuls must count L times
    L, B, D = 7, 16, 64
    def step(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), 0
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()
    f = jax.jit(step, in_shardings=(
        NamedSharding(mesh, P(None, None, "model")),
        NamedSharding(mesh, P("data", None))))
    txt = f.lower(jax.ShapeDtypeStruct((L, D, D), jnp.float32),
                  jax.ShapeDtypeStruct((B, D), jnp.float32)) \
        .compile().as_text()
    a = analyze_hlo_text(txt)
    # per-device dot: the walker resolves the scan's trip count statically,
    # so the cost must be L * one-layer flops EXACTLY (measured: 114688 =
    # 7 * 2*16*64*64/8) — bounded two-sided with a 2x fusion allowance,
    # and no loop may fall back to the unknown-trip-count estimate
    one_layer = 2 * B * D * D / 8           # most conservative (8 devices)
    assert L * one_layer * 0.9 <= a["flops_per_device"] <= L * one_layer * 2.0, a
    assert a["unknown_trip_counts"] == 0, a
    print("TRIPCOUNT_OK", a["flops_per_device"])

    # 2. pod-crossing classification: an all-reduce over ("pod",) crosses,
    # over ("model",) does not
    def pod_sum(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "pod"), mesh=mesh,
                             in_specs=P("pod"), out_specs=P(),
                             check_vma=False, axis_names={"pod"})(x)
    t1 = jax.jit(pod_sum).lower(
        jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile().as_text()
    assert pod_crossing_bytes(t1, pod_size=4) > 0, "pod psum must cross"

    def model_sum(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "model"), mesh=mesh,
                             in_specs=P("model"), out_specs=P(),
                             check_vma=False, axis_names={"model"})(x)
    t2 = jax.jit(model_sum).lower(
        jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile().as_text()
    assert pod_crossing_bytes(t2, pod_size=4) == 0, "model psum is intra-pod"
    print("POD_CLASSIFY_OK")

    # 3. sparse access: updating one row of a big buffer in a scan must not
    # charge the whole buffer per step
    N = 1024
    def writer(buf):
        def body(buf, i):
            return jax.lax.dynamic_update_index_in_dim(
                buf, jnp.ones((128,)), i, 0), 0
        buf, _ = jax.lax.scan(body, buf, jnp.arange(N, dtype=jnp.int32))
        return buf
    t3 = jax.jit(writer).lower(
        jax.ShapeDtypeStruct((N, 128), jnp.float32)).compile().as_text()
    a3 = analyze_hlo_text(t3)
    # per step the DUS touches one 512-byte row (plus indices/carries),
    # NOT the whole 512 KiB buffer.  Measured: ~2.66 MB total = ~5 rows'
    # worth per step; the bound allows 32x per-row overhead, still ~60x
    # tighter than charging the full buffer each step.
    row_bytes = 128 * 4
    assert N * row_bytes <= a3["bytes_per_device"] <= 32 * N * row_bytes, \
        f"sparse DUS miscounted: {a3}"
    assert a3["unknown_trip_counts"] == 0, a3
    print("SPARSE_OK", a3["bytes_per_device"])
""")


def test_serving_path_costs():
    """Pin the compiled cost of the device-resident serving path.

    ``benchmarks.roofline_table.serving_costs`` walks the REAL deployed
    entry points — the batched scan-fold per bucket and the coalesced
    K-way delivery merge per snapshot bucket.  Baselines (CPU, 64x8 f32
    arena, codec_width 8): scan bytes 8.5e3/1.0e5/7.6e5 at buckets
    1/8/64; aligned merge 1.1e4/7.6e4/1.5e5 at K=1/4/8; fallback merge
    2.2e5 at K=4.  The assertions pin the SHAPE of those numbers with
    margin, so a regression that reintroduces O(S^2) probing, loses a
    static trip count, or makes cost super-linear in bucket/K fails here.
    """
    from benchmarks.roofline_table import serving_costs

    rows = serving_costs()
    by = {(r["program"], r["size"]): r for r in rows}

    # every scan/merge loop must have a statically-known trip count —
    # an unknown count means the walker (and the roofline) is guessing
    for r in rows:
        assert r["unknown_trips"] == 0, r

    # scan-fold cost is ~linear in the batch bucket (measured 64/8 ratio
    # 7.57): super-linear growth would mean the fold re-reads the arena
    # per request instead of threading it through the carry
    scan8 = by[("jit_scan", "bucket=8")]["bytes"]
    scan64 = by[("jit_scan", "bucket=64")]["bytes"]
    assert 4.0 <= scan64 / scan8 <= 12.0, (scan8, scan64)

    # the slot-aligned elementwise merge must beat the O(S^2) argmax-probe
    # fallback decisively (measured 2.9x cheaper at K=4)
    al4 = by[("merge/aligned", "K=4")]["bytes"]
    fb4 = by[("merge/fallback", "K=4")]["bytes"]
    assert al4 < 0.6 * fb4, (al4, fb4)

    # coalesced K-way merge is ~linear in K (measured K8/K4 = 1.92):
    # doubling the folded snapshots may not much more than double cost
    al8 = by[("merge/aligned", "K=8")]["bytes"]
    assert al4 < al8 <= 3.0 * al4, (al4, al8)


@pytest.mark.slow
def test_walker_properties(tmp_path):
    script = tmp_path / "walker.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    for marker in ("TRIPCOUNT_OK", "POD_CLASSIFY_OK", "SPARSE_OK"):
        assert marker in res.stdout
