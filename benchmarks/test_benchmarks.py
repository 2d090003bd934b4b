"""Tests of the benchmark itself; they run on the CPU in a minute or two.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_benchmarks.py -q

1. The trace reduction against the small recorded trace in testdata/.
2. The control of each cell at the rehearsal size: the reference in the
   program's place, in fp8, has to come out as not correct.
3. A whole run (the look for a chip skipped by `--rehearse`) with the
   timed path broken underneath has to report `correct: false`, once for
   each fault a cell can have: a step that returns its state unchanged,
   half of the batch left out, a served token altered where it is
   produced.
"""

import gzip
import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as R  # noqa: E402
from benchmarks import trace_reduce as T  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN = "gpt2-medium.train-seq1024"
SERVE = "gpt2-medium.serve-closed-c64"


# ---------------------------------------------------------------------------
# 1. trace reduction
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "testdata",
                                "train_step_v5e.json.gz"), "rt") as f:
        return json.load(f)


def test_busy_union_merges_overlaps_and_lists_gaps():
    busy, gaps = T.busy_union([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert busy == 23
    assert gaps == [(12, 20)]


def test_op_names():
    name = ('%all-reduce.7 = f32[1024]{0} all-reduce(f32[1024]{0} %x), '
            'replica_groups={}')
    assert T.op_kind(name) == "all-reduce" and T.is_collective(name)
    assert T.op_shape(name) == "f32[1024]"
    assert not T.is_collective("%fusion.3 = bf16[8,4]{1,0} fusion(...)")
    assert T.is_mosaic('%jvp__.4 = (bf16[1,2]) custom-call(...), '
                       'custom_call_target="tpu_custom_call"')


def test_synthetic_two_chips_idle_and_collectives():
    ms = 1e6
    trace = {
        "/device:TPU:0": {"XLA Ops": [
            ["%fusion.1 = f32[8]{0} fusion()", 0, 40 * ms],
            ["%all-reduce.1 = f32[8]{0} all-reduce()", 50 * ms, 10 * ms]],
            "XLA Modules": [["jit_step(1)", 0, 60 * ms]]},
        "/device:TPU:1": {"XLA Ops": [
            ["%fusion.1 = f32[8]{0} fusion()", 0, 80 * ms]]},
        "/host:CPU": {"python3": [["bench:window", 0, 100 * ms],
                                  ["bench:fetch_loss", 38 * ms, 20 * ms]]},
    }
    r = T.reduce_trace(trace)
    assert r["chips"] == 2 and r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((0.05 + 0.08) / 2)
    assert r["idle_share"] == pytest.approx(0.35)
    assert r["collective_s"] == pytest.approx(0.01)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"fetch_loss": 0.01, "no_span": 0.04})


def test_recorded_trace(recorded):
    """Numbers read off the recorded trace by hand (see testdata/README)."""
    want = json.load(open(os.path.join(HERE, "testdata",
                                       "train_step_v5e.expected.json")))
    r = T.reduce_trace(recorded)
    assert r["chips"] == 1
    for key in ("window_s", "busy_s", "idle_share", "mosaic_s",
                "collective_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9, abs=1e-12), key
    assert len(r["mosaic_calls"]) == want["mosaic_calls"]
    assert r["device_ops"][0][0] == want["top_op"]
    assert 0.0 < r["idle_share"] < 1.0


# ---------------------------------------------------------------------------
# whole runs at the rehearsal size
# ---------------------------------------------------------------------------

def bench_run(capsys, tmp_path, workload, *extra):
    code = R.main(["--workload", workload, "--seed", "4000000007",
                   "--seconds", "1", "--rehearse", "--out", str(tmp_path),
                   *extra])
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def break_config(monkeypatch, **replace):
    """Make run.py's loader hand out the configuration's module with some
    of its builders wrapped: the timed path broken underneath."""
    load = R.load_module

    def loader(kind, name):
        mod = load(kind, name)
        if kind == "configs":
            for attr, wrap in replace.items():
                setattr(mod, attr, wrap(getattr(mod, attr)))
        return mod

    monkeypatch.setattr(R, "load_module", loader)


def rehearsal_limits(workload):
    limits = R.load_json("limits", workload + ".json")
    return dict(limits["limits"], **limits.get("rehearse_limits", {}))


def test_train_sound_run_is_correct_and_control_is_not(capsys, tmp_path):
    line = bench_run(capsys, tmp_path, TRAIN, "--control", "1")
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    limits = rehearsal_limits(TRAIN)
    for what in ("control_fp8", "control_half_batch"):
        got = line["notes"][what]
        assert any(got[n] > limits[n] for n in limits), (what, got)


def test_train_step_that_leaves_state_unchanged(capsys, tmp_path,
                                                monkeypatch):
    def wrap(build):
        def broken(cfg, job, seed):
            trainer = build(cfg, job, seed)
            step = trainer.step

            def no_update(x, y):
                state = trainer.state
                keep = type(state)(**{
                    f: getattr(state, f) for f in
                    ("params", "opt_state", "buffers", "step", "rng")})
                import jax
                keep = jax.tree.map(lambda a: a.copy(), keep)
                loss = step(x, y)
                trainer.state = keep
                return loss

            trainer.step = no_update
            return trainer
        return broken

    break_config(monkeypatch, build_trainer=wrap)
    line = bench_run(capsys, tmp_path, TRAIN)
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out(capsys, tmp_path, monkeypatch):
    def wrap(build):
        def broken(cfg, job, seed):
            trainer = build(cfg, job, seed)
            step = trainer.step
            trainer.step = lambda x, y: step(
                x.at[len(x) // 2:].set(x[:len(x) // 2]),
                y.at[len(y) // 2:].set(y[:len(y) // 2]))
            return trainer
        return broken

    break_config(monkeypatch, build_trainer=wrap)
    line = bench_run(capsys, tmp_path, TRAIN)
    assert line["correct"] is False


def test_serve_sound_run_is_correct_and_control_is_not(capsys, tmp_path):
    line = bench_run(capsys, tmp_path, SERVE, "--control", "1")
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["notes"]["control_fp8"] > \
        rehearsal_limits(SERVE)["token_logit_gap"]


def test_serve_token_altered_where_it_is_produced(capsys, tmp_path,
                                                  monkeypatch):
    def wrap(build):
        def broken(cfg, job, seed, clock):
            engine = build(cfg, job, seed, clock)
            resolve = engine._resolve_ok

            def altered(req, now):
                if len(req.tokens) > 2:
                    req.tokens[2] = (req.tokens[2] + 1) % cfg["vocab_size"]
                return resolve(req, now)

            engine._resolve_ok = altered
            return engine
        return broken

    break_config(monkeypatch, build_engine=wrap)
    line = bench_run(capsys, tmp_path, SERVE)
    assert line["correct"] is False


def test_no_tpu_means_no_result(capsys):
    code = R.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert code != 0 and out.out.strip() == ""
