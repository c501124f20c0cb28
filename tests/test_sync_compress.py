"""Compressed Eq. (8d) sync (--sync-compress {bf16,int8}).

 * codec properties: int8 dequant error bounded by half a quantization
   step per chunk; the fused Pallas quantize/dequant kernels match the
   jnp oracle bit-for-bit.
 * error feedback: on a FIXED tree the running mean of the dequantized
   payloads converges to the true value at O(1/K) — the residual
   telescopes the quantization error away over repeated syncs.
 * compiled-HLO byte accounting (subprocess, 8 host devices): the
   replica-axis sync collective carries <= 1/2 the f32 bytes at bf16
   and <= 1/4 (+ per-chunk scale overhead) at int8, via
   hlo_stats.collective_bytes_by_axis.
 * checkpoint round-trip under --sync-compress int8: the error-feedback
   residual rides the state; deployable(state) exact-equal after
   restore; training continues.
"""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ParleConfig
from repro.core import compress, parle, registry
from repro.kernels import ops as kops


# ------------------------------------------------------------------
# Codec units
# ------------------------------------------------------------------

def test_int8_quantize_error_bounded_per_chunk():
    key = jax.random.PRNGKey(0)
    c = compress.pad_to_chunk(
        jax.random.normal(key, (2, 5000)) * jnp.linspace(0.1, 30, 5000))
    q, s, res = compress.quantize_ef(c, "int8")
    assert q.dtype == jnp.int8
    chunked = np.asarray(c).reshape(2, -1, compress.CHUNK)
    step = np.asarray(s)[..., None]          # scale = one int8 step
    assert np.all(np.abs(np.asarray(res).reshape(chunked.shape))
                  <= step / 2 + 1e-7)


def test_bf16_quantize_is_cast_roundtrip():
    c = compress.pad_to_chunk(jax.random.normal(jax.random.PRNGKey(1),
                                                (1, 3000)))
    q, s, res = compress.quantize_ef(c, "bf16")
    assert q.dtype == jnp.bfloat16 and s is None
    np.testing.assert_array_equal(
        np.asarray(res), np.asarray(c - q.astype(jnp.float32)))


def test_quantize_kernel_matches_oracle():
    c = compress.pad_to_chunk(
        jax.random.normal(jax.random.PRNGKey(2), (3, 20000)) * 7.0)
    w_q, w_s, w_e = compress.quantize_ef(c, "int8")
    g_q, g_s, g_e = kops.quantize_ef(c)
    # the wire payload (q, scales) must be BIT-identical — it decides
    # the dequantized mean everywhere; the residual may differ by one
    # FMA contraction (c - q*s fuses differently per context)
    np.testing.assert_array_equal(np.asarray(w_q), np.asarray(g_q))
    np.testing.assert_array_equal(np.asarray(w_s), np.asarray(g_s))
    np.testing.assert_allclose(np.asarray(w_e), np.asarray(g_e),
                               rtol=1e-6, atol=1e-6)


def test_dequant_update_kernel_matches_composed_oracle():
    """The fused dequantize+mean+update kernel == dequantize -> mean ->
    parle_sync_update oracle."""
    from repro.kernels import ref
    key = jax.random.PRNGKey(3)
    r, n, m = 2, 4, 2 * compress.PAD_MULTIPLE
    ks = jax.random.split(key, 5)
    x, z, v = [jax.random.normal(k, (r, m)) for k in ks[:3]]
    c = jax.random.normal(ks[3], (n, m)) * 3.0
    q, s = compress.quantize(c, "int8")
    scal = dict(gamma_scale=1.0, inv_rho=2.0, lr=0.1, mu=0.9)
    xbar = jnp.mean(compress.dequantize(q, s, "int8"), axis=0)
    want = ref.parle_sync_update(x, z, v, xbar[None], **scal)
    from repro.kernels.parle_update import parle_sync_dequant_flat
    got = parle_sync_dequant_flat(x, z, v, q,
                                  s.reshape(n, -1),
                                  jnp.asarray([1.0, 2.0, 0.1, 0.9],
                                              jnp.float32),
                                  interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(w), np.asarray(g),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------
# Error feedback
# ------------------------------------------------------------------

@pytest.mark.parametrize("method", ["bf16", "int8"])
def test_error_feedback_drives_quantization_error_to_zero(method):
    """Fixed contribution c: with the residual carried across syncs,
    dequant(q_k) = c + e_k - e_{k+1}, so the running mean of the
    payloads telescopes to c at O(1/K) — while a feedback-free codec
    plateaus at its quantization floor."""
    key = jax.random.PRNGKey(4)
    c = compress.pad_to_chunk(
        (jax.random.normal(key, (1, 4000)) * 13.7).reshape(1, -1))
    e = jnp.zeros_like(c)
    acc = jnp.zeros_like(c)
    errs = []
    for k in range(1, 33):
        q, s, e = compress.quantize_ef(c + e, method)
        acc = acc + compress.dequantize(q, s, method)
        errs.append(float(jnp.max(jnp.abs(acc / k - c))))
    # O(1/K): 32 syncs shrink the worst-leaf error by ~the sync count
    assert errs[-1] < errs[0] / 8, errs[::8]
    # the residual stays bounded (no drift)
    assert float(jnp.max(jnp.abs(e))) < float(jnp.max(jnp.abs(c))) * 0.01


def test_sync_step_carries_residual_and_stays_near_mean():
    cfg = ParleConfig(n_replicas=4, L=1, batches_per_epoch=10,
                      sync_compress="int8")
    key = jax.random.PRNGKey(5)
    state = parle.init_from_replicas(
        {"w": jax.random.normal(key, (4, 300)) * 5.0}, cfg)
    assert state.e is not None
    out = parle.sync_step(state, cfg)
    assert out.e is not None
    # with gamma_scale=1, inv_rho small...: just sanity — the residual
    # is exactly c - dequant(c) for the first sync (e started at 0)
    c = compress.pad_to_chunk(np.asarray(state.x["w"]).reshape(4, -1))
    q, s, res = compress.quantize_ef(jnp.asarray(c), "int8")
    np.testing.assert_allclose(np.asarray(out.e["w"]),
                               np.asarray(res[:, :300]), rtol=1e-6)


def test_compressed_local_trajectory_matches_uncompressed_loosely():
    """int8+EF is lossy per sync but must track the uncompressed
    trajectory closely on a smooth problem."""
    algo = registry.get("parle")

    def loss(p, b):
        return jnp.mean((p["w"] - b["t"]) ** 2), ()

    params = {"w": jax.random.normal(jax.random.PRNGKey(6), (64,))}
    batch = {"t": jnp.zeros((2, 64))}
    outs = {}
    for method in ("none", "int8"):
        cfg = ParleConfig(n_replicas=2, L=2, lr=0.05, lr_inner=0.05,
                          batches_per_epoch=10, sync_compress=method)
        state = algo.init(params, cfg)
        step = jax.jit(algo.make_step(loss, cfg))
        for i in range(8):
            state, m = step(state, batch)
        outs[method] = np.asarray(algo.deployable(state)["w"])
    np.testing.assert_allclose(outs["int8"], outs["none"],
                               rtol=5e-3, atol=5e-3)


# ------------------------------------------------------------------
# Checkpoint round-trip with the residual leaf (satellite)
# ------------------------------------------------------------------

def test_int8_checkpoint_roundtrip_resumes_training():
    algo = registry.get("parle")
    cfg = ParleConfig(n_replicas=2, L=2, lr=0.05, lr_inner=0.05,
                      batches_per_epoch=10, sync_compress="int8",
                      precision="bf16")

    def loss(p, b):
        return jnp.mean((p["w"] - b["t"]) ** 2), ()

    params = {"w": jax.random.normal(jax.random.PRNGKey(7), (40,))}
    batch = {"t": jax.random.normal(jax.random.PRNGKey(8), (2, 40))}
    state = algo.init(params, cfg)
    step = jax.jit(algo.make_step(loss, cfg))
    for i in range(4):                       # crosses 2 sync boundaries
        state, _ = step(state, batch)
    assert float(jnp.max(jnp.abs(state.e["w"]))) > 0   # EF active
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "int8.npz")
        ckpt.save(path, state, step=4, algo="parle")
        restored = ckpt.restore(path, algo.init(params, cfg), algo="parle")
    # residual restored bit-exactly; deployable exact-equal
    np.testing.assert_array_equal(np.asarray(state.e["w"]),
                                  np.asarray(restored.e["w"]))
    np.testing.assert_array_equal(
        np.asarray(algo.deployable(state)["w"]),
        np.asarray(algo.deployable(restored)["w"]))
    # training continues — and identically to the unsaved state
    s_a, m_a = step(state, batch)
    s_b, m_b = step(restored, batch)
    np.testing.assert_array_equal(np.asarray(s_a.x["w"]),
                                  np.asarray(s_b.x["w"]))


# ------------------------------------------------------------------
# Compiled-HLO byte accounting (subprocess, 8 host devices)
# ------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 8
    from repro.configs.base import ParleConfig
    from repro.core import parle
    from repro.launch.mesh import make_mesh_from_spec
    from repro.launch import hlo_stats

    def loss(p, b):
        return 0.5 * jnp.sum((p["w"] - b["t"]) ** 2), ()

    size = 16384
    mesh = make_mesh_from_spec("replica:8")
    batch = {"t": jnp.zeros((8, 1), jnp.float32)}
    payload = {}
    for method in ("none", "bf16", "int8"):
        cfg = ParleConfig(n_replicas=8, L=2, batches_per_epoch=10,
                          sync_compress=method)
        st = parle.init({"w": jnp.zeros((size,), jnp.float32)}, cfg)
        step = parle.make_sharded_train_step(loss, cfg, mesh)
        txt = step.lower(st, batch).compile().as_text()
        stats = hlo_stats.collective_bytes_by_axis(txt, dict(mesh.shape))
        rep = stats["by_axis"]["replica"]
        # strip the 4-byte scalar loss pmean: what remains is the sync
        payload[method] = sum(rep.values()) - 4
        print(method, rep, stats["counts_by_axis"])

    base = payload["none"]
    assert base == size * 4, payload            # f32 model-size sync
    assert payload["bf16"] <= base // 2, payload
    scales = (size // 1024) * 4                 # one f32 scale per chunk
    assert payload["int8"] <= base // 4 + scales, payload
    print("BYTES_OK", payload)

    # compressed trajectories: local == replica-sharded, bit for bit
    # (quantization is per replica, so placement cannot change it)
    for method in ("bf16", "int8"):
        cfg = ParleConfig(n_replicas=8, L=2, batches_per_epoch=10,
                          sync_compress=method)
        key = jax.random.PRNGKey(0)
        reps = {"w": jax.random.normal(key, (8, 6))}
        b = {"t": jax.random.normal(jax.random.PRNGKey(1), (8, 1))}
        st_l = parle.init_from_replicas(reps, cfg)
        st_s = parle.init_from_replicas(reps, cfg)
        stepl = jax.jit(parle.make_train_step(loss, cfg))
        steps = parle.make_sharded_train_step(loss, cfg, mesh)
        for i in range(5):
            st_l, _ = stepl(st_l, b)
            st_s, _ = steps(st_s, b)
        np.testing.assert_array_equal(np.asarray(st_l.x["w"]),
                                      np.asarray(st_s.x["w"]))
        np.testing.assert_array_equal(np.asarray(st_l.e["w"]),
                                      np.asarray(st_s.e["w"]))
    print("LAYOUT_INVARIANT_OK")
""")


def _run_child(code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=420)


@pytest.fixture(scope="module")
def compress_child():
    return _run_child(_CHILD)


def test_compressed_sync_collective_bytes(compress_child):
    """Acceptance: the replica-axis sync collective carries <= 1/2x
    bytes at bf16 and <= 1/4x (+ scales) at int8 versus f32, from
    compiled HLO."""
    assert compress_child.returncode == 0, \
        f"stdout:\n{compress_child.stdout}\nstderr:\n{compress_child.stderr}"
    assert "BYTES_OK" in compress_child.stdout


def test_compressed_sync_layout_invariant(compress_child):
    assert compress_child.returncode == 0, \
        f"stdout:\n{compress_child.stdout}\nstderr:\n{compress_child.stderr}"
    assert "LAYOUT_INVARIANT_OK" in compress_child.stdout
