"""A whole run on the CPU at a small size, past the harness's look for a chip:
the control, judged by the run's own comparison, comes out not correct; a token
altered where the engine produces it reads not correct; what the run prints
before its result; and multi-turn sessions on four replicas."""
import json
import time

import pytest

from bench.harness import runner, spec

# small dense GQA model of the starcoder2 family, served chunked as the cells are
CONFIG = dict(name="small", arch="starcoder2-7b", model="decoder", family="dense", n_layers=2,
              d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, vocab=512,
              n_experts=0, act="gelu", norm="layernorm", rope_theta=1e6,
              tie_embeddings=True, dtype="bfloat16",
              quant=dict(bits=8, alpha=0.15, kv_bits=8, calibration=[2, 64]))
TRAFFIC = dict(loop="open", rate_per_s=10.0, n_requests=300, block=8, sizes_seed=3,
               shared=dict(count=2, length=16, share=0.5),
               prompt=dict(dist="uniform", lo=8, hi=40),
               output=dict(dist="uniform", lo=8, hi=16),
               engine=dict(batch_size=4, max_len=96, chunked=True, token_budget=32,
                           kv_cache="int8", cache_layout="paged", path="fused-int8",
                           scheduler="continuous"))
# Readings of this cell on five seeds (CPU): served tokens 0.0027-0.0216, the int4
# control 0.310-0.474.
LIMITS = {"correct": {"limits": {"max_logit_gap": 0.08}, "sample_tokens": 48,
                      "max_requests": 4, "min_tokens": 24}}
SEED = 2**31 + 99


def _cell():
    b = spec.load_benchmark()
    return spec.Cell(name="small", chips=1, config_name="small", config=CONFIG,
                     model=spec.model_module("decoder"), traffic_name="small",
                     traffic=TRAFFIC, end_to_end=b["end_to_end"], per_layer=b["per_layer"])


def _run(**kw):
    return runner.run_cell(_cell(), SEED, 3.0, False, t_process=time.monotonic(),
                           require_tpu=False, limits=LIMITS, **kw)


@pytest.fixture(scope="module")
def sound():
    return _run(control=True)


def test_sound_run_is_correct_and_control_is_not(sound):
    assert sound["correct"] is True
    gap = sound["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert sound["control"]["max_logit_gap"] > 3 * gap["value"]
    # the control goes through the same comparison and comes out not correct
    assert sound["control"]["correct"] is False
    ctl = sound["control"]["check"]["max_logit_gap"]
    assert ctl["value"] > ctl["limit"] == gap["limit"]
    assert list(sound)[-1] == "check"
    assert {"output_tok_s", "itl_p95_ms", "setup_s"} <= set(sound["metrics"])
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_run_prints_lateness_and_window_compiles(capfd):
    _run()
    err = capfd.readouterr().err
    assert "bench: generator lateness: max" in err
    assert "bench: compiles inside the window: 0" in err
    assert err.rstrip().splitlines()[-1].startswith("check ")


def test_unwarmed_shapes_count_as_window_compiles(monkeypatch, capfd):
    async def no_warm_up(*a, **k):
        return None
    monkeypatch.setattr(runner, "_warm_up", no_warm_up)
    _run()
    err = capfd.readouterr().err
    line = next(ln for ln in err.splitlines() if "compiles inside the window" in ln)
    assert int(line.rsplit(":", 1)[1]) > 0


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serving import engine as E

    emit = E.ServeEngine._emit

    def altered(self, slot, tok, finished):
        r = self._slots[slot]
        if len(r.out) == 3:                      # the fourth token of every request
            tok = (tok + 1) % CONFIG["vocab"]
        return emit(self, slot, tok, finished)

    monkeypatch.setattr(E.ServeEngine, "_emit", altered)
    out = _run()
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > LIMITS["correct"]["limits"]["max_logit_gap"]


# multi-turn sessions on four replicas behind the affinity router, each
# replica pinned to its own virtual CPU device as the sessions cell pins one to
# each chip; run in a child process, which can be given four devices
SESSIONS = dict(loop="sessions", clients=8, n_sessions=64, block=8, stagger=True,
                sizes_seed=5,
                turns=dict(dist="uniform", lo=2, hi=3),
                first=dict(dist="uniform", lo=8, hi=24),
                user=dict(dist="uniform", lo=4, hi=12),
                output=dict(dist="uniform", lo=4, hi=8),
                engine=dict(batch_size=2, max_len=96, chunked=True, token_budget=32,
                            kv_cache="int8", cache_layout="paged", path="fused-int8",
                            scheduler="continuous", prefix_reuse=True))


def _sessions_run():
    b = spec.load_benchmark()
    cell = spec.Cell(name="small.sessions", chips=4, config_name="small", config=CONFIG,
                     model=spec.model_module("decoder"), traffic_name="sessions",
                     traffic=SESSIONS, end_to_end=b["end_to_end"],
                     per_layer=b["per_layer"])
    return runner.run_cell(cell, SEED, 5.0, False, t_process=time.monotonic(),
                           require_tpu=False, limits=LIMITS)


def _child(mode: str):
    import os
    import subprocess
    import sys

    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(spec.ROOT), str(spec.ROOT / "src")]))
    p = subprocess.run([sys.executable, __file__, mode], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_sessions_on_four_replicas_are_correct_and_reuse_their_history():
    out, err = _child("sound")
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4
    assert {"output_tok_s", "itl_p95_ms", "setup_s", "ttft_p95_ms"} <= set(out["metrics"])
    assert "bench: compiles inside the window: 0" in err
    # the history of the clients' opening sessions went out in set-up, and the
    # window opened after the opening turns' first tokens
    sent = next(ln for ln in err.splitlines() if "history, sent in set-up" in ln)
    assert int(sent.rsplit(":", 1)[1].split()[0]) > 0
    assert "opening_s" in next(ln for ln in err.splitlines() if ln.startswith("bench: set-up"))
    line = next(ln for ln in err.splitlines() if "server counters over the" in ln)
    assert "affinity_hits" in line
    assert "prefix_tokens_reused" in next(
        ln for ln in err.splitlines() if "engine counters over the" in ln)
    # the sample is drawn across the replicas
    used = next(ln for ln in err.splitlines() if ln.startswith("bench: reference over"))
    reps = used.split("finished on replicas [", 1)[1].split("]", 1)[0]
    assert len(reps.split(",")) > 1


def test_sessions_token_altered_where_produced_is_not_correct():
    out, _ = _child("altered")
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > LIMITS["correct"]["limits"]["max_logit_gap"]


def _altered_emit():
    from repro.serving import engine as E

    emit = E.ServeEngine._emit

    def altered(self, slot, tok, finished):
        if len(self._slots[slot].out) == 2:       # the third token of every answer
            tok = (tok + 1) % CONFIG["vocab"]
        return emit(self, slot, tok, finished)

    E.ServeEngine._emit = altered


if __name__ == "__main__":
    import sys

    if sys.argv[1] == "altered":
        _altered_emit()
    print(json.dumps(_sessions_run()), flush=True)
