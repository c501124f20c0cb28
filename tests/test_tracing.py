"""Layer names on the profiler's clock: the program's ``jax.named_scope``
layers reach the compiled round's HLO ``op_name`` metadata on both
update paths, ``obs`` spans become host annotations of a
``jax.profiler.trace``, the tracer maps a compiled program's
instructions to their scopes, and the round runner's host work between
rounds is spanned."""
import glob
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ModelConfig, ParleConfig
from repro.core import parle
from repro.data.synthetic import TokenStream, make_round_batch_fn
from repro.models.model import build_model
from repro.obs import NULL_SPAN, Obs
from repro.obs.trace import hlo_op_names
from repro.runtime import RoundRunner

CFG = ModelConfig(name="t-ssm", family="ssm", num_layers=2, d_model=64,
                  num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=128,
                  ssm_state=16, ssm_head_dim=16, ssm_chunk=16)
L, N, B, T = 2, 2, 1, 32
ROUND_SCOPES = ("parle_inner", "parle_sync", "model", "ssd", "in_proj",
                "conv", "out_proj", "embed", "head_loss")


def _round(use_kernel):
    """A tiny Mamba2 Parle job: the compiled fused round, its state and
    its round stager."""
    model = build_model(CFG)
    pcfg = ParleConfig(n_replicas=N, L=L, lr=0.1, lr_inner=0.1)
    state = parle.dealias_state(parle.init(model.init(jax.random.PRNGKey(0)),
                                           pcfg))
    stage = make_round_batch_fn(TokenStream(vocab_size=CFG.vocab_size,
                                            seq_len=T, batch_size=B, seed=3),
                                L, B, N)
    fn = parle.make_round_fn(model.loss, pcfg, use_kernel=use_kernel)
    return fn.lower(state, stage(0)).compile(), state, stage


@pytest.fixture(scope="module", params=[False, True], ids=["xla", "kernel"])
def compiled_round(request):
    return _round(request.param)


def _scopes_in(op_name):
    """The components of an op_name path, unwrapped from their
    transformations (``vmap(transpose(jvp(model)))`` -> ``model``)."""
    out = []
    for part in op_name.split("/"):
        m = re.match(r"^[\w\-.]+\((.*)\)$", part)
        while m:
            part = m.group(1)
            m = re.match(r"^[\w\-.]+\((.*)\)$", part)
        out.append(part)
    return out


def test_round_hlo_carries_every_layer_scope(compiled_round):
    compiled, _, _ = compiled_round
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    seen = {p for n in names for p in _scopes_in(n)}
    missing = [s for s in ROUND_SCOPES if s not in seen]
    assert not missing, missing
    # one scope covers a layer's forward and its backward
    assert any("transpose(jvp(model))" in n and "/in_proj/" in n
               for n in names)
    assert any("jvp(model)" in n and "transpose" not in n
               and "/ssd/" in n for n in names)


def test_hlo_op_names_maps_every_instruction_with_metadata(compiled_round):
    compiled, _, _ = compiled_round
    text = compiled.as_text()
    module, ops = hlo_op_names(text)
    assert module == "jit_round_fn"
    with_meta = re.findall(r"^\s+(?:ROOT )?%?(\S+) = .*op_name=\"([^\"]*)\"",
                           text, re.M)
    assert with_meta and dict(with_meta) == ops
    assert any("parle_inner" in _scopes_in(v) for v in ops.values())


def _quad_loss(p, b):
    return jnp.mean((p["w"] @ p["m"] - b["t"]) ** 2), ()


@pytest.mark.parametrize("kind", ["barrier", "overlap", "async",
                                  "replica_mesh"])
def test_round_keeps_the_parle_scopes(kind):
    """Every round program keeps the scopes the benchmark splits a round
    by: ``parle_inner`` (the inner update) in each, ``parle_sync`` in
    each round that syncs and in no other."""
    from repro.core import registry
    from repro.launch.mesh import make_mesh_from_spec
    pcfg = ParleConfig(n_replicas=N, L=L, lr=0.1, lr_inner=0.1,
                       sync_overlap=kind == "overlap")
    state = parle.dealias_state(parle.init(
        {"w": jnp.ones((8, 16)) * 0.1, "m": jnp.ones((16, 4)) * 0.1}, pcfg))
    batches = {"t": jnp.ones((L, N, 8, 4))}
    if kind == "async":
        fn = parle.make_inner_round_fn(_quad_loss, pcfg)
    else:
        mesh = make_mesh_from_spec("replica:1") \
            if kind == "replica_mesh" else None
        fn = registry.get("parle").make_round_fn(_quad_loss, pcfg, mesh=mesh)
    text = fn.lower(state, batches).compile().as_text()
    seen = {p for n in re.findall(r'op_name="([^"]*)"', text)
            for p in _scopes_in(n)}
    assert "parle_inner" in seen
    assert ("parle_sync" in seen) == (kind != "async")


def test_stager_runs_under_its_scope():
    stage = make_round_batch_fn(TokenStream(vocab_size=64, seq_len=8,
                                            batch_size=1, seed=1), 2, 1, 2)
    text = stage.lower(0).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("stage" in _scopes_in(n) for n in names)


def _host_events(logdir):
    pd = ProfileData.from_file(
        glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0])
    return [(e.name, dict(e.stats), e.start_ns, e.start_ns + e.duration_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]


def test_enabled_span_is_a_host_annotation_with_its_attributes(tmp_path):
    on = Obs(trace_out=str(tmp_path / "spans.json"))
    off = Obs()
    with jax.profiler.trace(str(tmp_path / "prof")):
        with on.span("round", round=7, step=175) as sp:
            sp.block(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
        with off.span("unseen") as nsp:
            assert nsp is NULL_SPAN
    events = _host_events(tmp_path / "prof")
    rounds = [e for e in events if e[0] == "round"]
    assert len(rounds) == 1
    assert rounds[0][1]["round"] == 7 and rounds[0][1]["step"] == 175
    # the device's work on the span's clock: the annotation closes after
    # the block, and lasts as long as the span's own timing
    assert rounds[0][3] - rounds[0][2] >= 0.5 * sp.dur_s * 1e9
    assert not [e for e in events if e[0] == "unseen"]
    assert off.tracer.events == []
    assert [e["name"] for e in on.tracer.events] == ["round"]


def test_runner_spans_round_stage_progress_and_maps_the_round(tmp_path):
    """Spans: ``round`` with ``stage`` (the prefetch) inside it, then
    ``progress``; the collected trace holds the compiled round's
    ``hlo_ops`` map, which names the device ops of a profile of those
    rounds."""
    compiled, state, stage = _round(False)
    spans = str(tmp_path / "spans.json")
    obs = Obs(trace_out=spans)
    runner = RoundRunner(obs)
    with jax.profiler.trace(str(tmp_path / "prof")):
        state, hist = runner.run_rounds(
            state, compiled, stage, start=0, rounds=3, L=L,
            tokens_per_round=L * N * B * T, aot=False,
            progress=lambda step, r, st, m: {"step": step})
        jax.block_until_ready(state)
    obs.finalize()
    assert [h["step"] for h in hist] == [2, 4, 6]
    with open(spans) as f:
        events = json.load(f)["traceEvents"]
    x = [e for e in events if e["ph"] == "X"]
    rounds = [e for e in x if e["name"] == "round"]
    stages = [e for e in x if e["name"] == "stage"]
    assert len(rounds) == 3 and len(stages) == 3
    assert len([e for e in x if e["name"] == "progress"]) == 3
    # the prefetch of rounds 2 and 3 lies inside rounds 1 and 2
    for r, s in zip(rounds[:2], stages[1:]):
        assert r["ts"] <= s["ts"] and s["args"]["depth"] == 1
        assert s["ts"] + s["dur"] <= r["ts"] + r["dur"] + 1.0
    maps = [e for e in events if e["ph"] == "M" and e["name"] == "hlo_ops"]
    assert len(maps) == 1
    ops = maps[0]["args"]["ops"]
    assert maps[0]["args"]["hlo_module"] == "jit_round_fn"
    # the round's op events in a CPU profile name instructions of its
    # HLO, and the map names the scope of every one that has a scope
    text = compiled.as_text()
    instructions = set(re.findall(r"^\s+(?:ROOT )?%?(\S+) = ", text, re.M))
    host = _host_events(tmp_path / "prof")
    ran = {st["hlo_op"] for _, st, _, _ in host
           if st.get("hlo_module") == "jit_round_fn"}
    assert ran and ran <= instructions
    unmapped = ran - set(ops)
    assert not [op for op in unmapped
                if re.search(rf"^\s+(?:ROOT )?%?{re.escape(op)} = .*op_name=",
                             text, re.M)]
    scoped = {p for op in ran & set(ops) for p in _scopes_in(ops[op])}
    assert {"parle_inner", "parle_sync", "model"} <= scoped
    # the spans are host annotations on the profile's clock
    names = {e[0] for e in host}
    assert {"round", "stage", "progress"} <= names
