"""Architecture modules: a configuration names its module under
``reference``, and the Parle reference, the counts and the scopes all go
through it.  The Mamba2 module's reference losses are pinned; a toy
module in ``data/`` plugs in with no edit to a harness file."""
import copy
import shutil
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import flops, harness, scopes
from bench import trace as bench_trace
from bench.reference import parle as ref_parle
from bench.traffic import tokens

DATA = Path(__file__).resolve().parent / "data"
TOY = {"reference": "toy_mlp",
       "model": {"width": 8, "hidden": 16, "vocab_size": 16}}


def small_job(L, batch, seq):
    job = copy.deepcopy(harness.cell_spec("train-mamba2-s12")["traffic"])
    job.update(L=L, batch=batch, seq=seq)
    return job


def test_unknown_architecture_names_the_path_it_looked_for():
    with pytest.raises(FileNotFoundError, match="reference/no_such_arch.py"):
        harness.architecture({"reference": "no_such_arch"})


def test_mamba2_reference_losses_are_pinned():
    """The replica-mean loss of every inner step of 2 rounds of a tiny
    Mamba2, pinned on the CPU: a change to the reference's arithmetic
    shows here."""
    conf = copy.deepcopy(harness.cell_spec("train-mamba2-s12")["config"])
    conf["model"].update(harness.architecture(conf).CPU_SIZE)
    seed = tokens.train_seed(2**31 + 17)
    got = ref_parle.run(conf, small_job(3, 2, 32), seed, 2)["losses"]
    assert got == [5.519349813461304, 5.274285078048706, 5.3429694175720215,
                   5.171731472015381, 5.165663242340088, 5.250208616256714]


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(harness, "ARCHITECTURES", DATA)
    return harness.architecture(TOY)


def test_toy_architecture_drives_the_parle_reference(toy):
    job, seed = small_job(2, 2, 8), 7
    ref = ref_parle.run(TOY, job, seed, 2)
    assert len(ref["losses"]) == 4
    # the first step is the toy's loss at its seeded weights, on each
    # replica's rows
    p = toy.init(jax.random.PRNGKey(seed), TOY["model"])
    first = [float(toy.loss(p, TOY["model"], *tokens.rows(seed, 0, a, 2, 2, 8,
                                                          16)))
             for a in range(2)]
    assert ref["losses"][0] == pytest.approx(sum(first) / 2, rel=1e-6)
    leaves = {"['embed']", "['w1']", "['w2']", "['head']"}
    assert [set(g) for g in ref["grad"]] == [leaves, leaves]
    assert all(v > 0 for c in ref["change"] for v in c.values())


def test_toy_architecture_drives_the_counts(toy):
    assert flops.train_per_token(TOY) == 3 * (2 * 2 * 8 * 16 + 2 * 8 * 16)
    assert sorted(flops.param_sizes(TOY)) == [16 * 8, 8 * 16, 16 * 8, 8 * 16]


def test_toy_architecture_scope_is_its_own(toy):
    """The ops of the toy's MLP, differentiated under ``model`` as the
    trainer does, land in ``toy_mlp``; without its module, in ``model``."""
    harness.add_src()
    from repro.obs.trace import hlo_op_names

    m = TOY["model"]
    p = toy.init(jax.random.PRNGKey(0), m)
    rows = tokens.rows(0, 0, 0, 1, 2, 8, 16)

    def model_loss(p):
        with jax.named_scope("model"):
            return toy.loss(p, m, *rows)

    text = jax.jit(jax.grad(model_loss)).lower(p).compile().as_text()
    names = list(hlo_op_names(text)[1].values())
    mlp = [n for n in names if "/toy_mlp/" in n]
    assert mlp
    assert scopes.of_config(TOY) == ("model", "parle_inner", "parle_sync",
                                     "toy_mlp")
    assert scopes.in_model(TOY) == ("model", "toy_mlp")
    assert {scopes.scope_of(n, scopes.of_config(TOY)) for n in mlp} \
        == {"toy_mlp"}
    assert {scopes.scope_of(n, scopes.SCOPES) for n in mlp} == {"model"}


def test_scopes_are_the_trainers_and_each_architectures():
    conf = harness.cell_spec("train-mamba2-s12")["config"]
    assert scopes.SCOPES == ("model", "parle_inner", "parle_sync")
    assert scopes.in_model(conf) == ("model", "embed", "in_proj", "conv",
                                     "ssd", "out_proj", "head_loss")
    assert scopes.of_benchmark() == scopes.of_config(conf) \
        == scopes.SCOPES + scopes.in_model(conf)[1:]
    # the SSD's op is Mamba2's ``ssd`` only where Mamba2's scopes are known
    op = "jit(round_fn)/while/body/vmap(transpose(jvp(model)))/ssd/mul"
    assert scopes.scope_of(op, scopes.of_config(conf)) == "ssd"
    assert scopes.scope_of(op, scopes.SCOPES) == "model"


def test_readers_reach_the_module_through_the_run(tmp_path):
    """The training kind hands the readers the configuration; on the small
    recorded trace of ``record_scopes.py`` (a ``model`` and a
    ``parle_inner`` scope, 3 calls of 4 steps) they read through it."""
    xprof = tmp_path / "xprof"
    xprof.mkdir()
    shutil.copy(DATA / "scopes.xplane.pb", xprof / "run.xplane.pb")
    conf = harness.cell_spec("train-mamba2-s12")["config"]
    art = {"kind": "train", "config": conf, "xprof": xprof,
           "spans": DATA / "scopes.spans.json", "chips": 1, "rounds": 3,
           "args": SimpleNamespace(L=4, replicas=2, use_kernel=True),
           "tokens_per_round": 4096, "window_s": 2.0,
           "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
           "trace": bench_trace.reduce(str(xprof / "run.xplane.pb"), 1)}
    red = scopes.reduce(str(xprof / "run.xplane.pb"),
                        str(DATA / "scopes.spans.json"), 1,
                        scopes.of_config(conf))
    read = harness.load_metric_reader
    assert red["scope_s"]["model"] > 0 and red["scope_s"]["parle_inner"] > 0
    assert read("model_ms.train")(art) == pytest.approx(
        1e3 * red["scope_s"]["model"] / 12)
    assert read("inner_update_ms.train")(art) == pytest.approx(
        1e3 * red["scope_s"]["parle_inner"] / 12)
    assert read("mfu.train")(art) == pytest.approx(
        100 * 703_317_504 * 3 * 4096 / 2.0 / 197e12)
    # the recorded kernel is not Parle's: nothing to read
    assert read("parle_update_roofline")(art) is None
