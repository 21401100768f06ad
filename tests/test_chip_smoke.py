"""``chip_smoke.py``'s phases rehearsed on the CPU at smoke widths.

On the chip the script serves qwen3-4b at full width and compiles the
kernels with ``interpret=False``; here the same phase functions run the
smoke config and interpret mode, so a change that breaks the smoke's
checks fails before it reaches the chip.  On the CPU in float32 every
engine gives the same greedy tokens, so the divergence reports must say so.
"""
import importlib.util
import os

import pytest

from repro import obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENS = (8, 12, 20, 33, 40)
OPS = {"n": 8192, "mm": (128, 256, 384), "rows": 64, "d": 256}
KERNELS = {"tokens": 128, "d": 256, "ff": 512, "heads": 4, "kv_heads": 2,
           "head_dim": 128}
PREFILL = ({"name": "gqa8", "heads": 8, "kv_heads": 1, "head_dim": 128,
            "tokens": 512},
           {"name": "packed64", "heads": 8, "kv_heads": 2, "head_dim": 64,
            "tokens": 256, "scale": 1 / 64})
PAGED = ({"name": "gqa4", "layers": 3, "pages": 24, "kv_heads": 8,
          "max_seq": 160},
         {"name": "packed64", "layers": 2, "pages": 40, "kv_heads": 8,
          "max_seq": 160, "slots": 16, "head_dim": 64, "scale": 1 / 64})


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases(capsys):
    smoke = _chip_smoke()
    obs.metrics_reset()  # the phases require zero degradations/fallbacks
    clock = smoke.CompileClock()
    smoke.phase_serve(clock, smoke=True, lens=LENS)
    ran = smoke.phase_kernels(clock, sizes=OPS, ksizes=KERNELS,
                              psizes=PAGED, fsizes=PREFILL,
                              interpret=True)
    out = capsys.readouterr().out
    assert ran == ["dot", "asum", "scal", "matmul", "rmsnorm", "softmax",
                   "pallas matmul", "pallas rmsnorm",
                   "pallas flash_attention",
                   "pallas flash_attention (prefill)",
                   "pallas paged_decode_attention"]
    n = len(LENS) * smoke.MAX_NEW
    assert f"ContinuousEngine(paged): teacher-forced through Model.forward, " \
           f"{n} of {n} tokens are the forward argmax" in out
    assert f"same tokens for {len(LENS)} of {len(LENS)} requests" in out


def test_teacher_forcing_fails_tokens_the_model_would_not_emit():
    smoke = _chip_smoke()
    from repro.launch import serve as serve_mod

    model, params = serve_mod.load_model(smoke.ARCH, smoke=True)
    reqs = serve_mod.make_requests(model.cfg, LENS[:2], smoke.MAX_NEW)
    engine = serve_mod.make_engine(model, params, max_seq=smoke.MAX_SEQ,
                                   slots=2)
    outs, _ = serve_mod.serve(engine, reqs)
    smoke.teacher_force(model, params, reqs, outs, "engine")
    vocab = model.cfg.vocab
    wrong = [[(t + vocab // 2) % vocab for t in o] for o in outs]
    with pytest.raises(SystemExit):
        smoke.teacher_force(model, params, reqs, wrong, "engine")


def test_required_identity_fails_on_a_divergence(capsys):
    smoke = _chip_smoke()
    ref = [[1, 2, 3], [4, 5, 6]]
    scored = [([0.0] * 3, [0.5] * 3)] * 2
    assert smoke.report_divergences("a vs b", ref, ref, scored, scored,
                                    require=True) == 2
    got = [[1, 2, 3], [4, 7, 6]]
    assert smoke.report_divergences("a vs b", got, ref, scored, scored) == 1
    with pytest.raises(SystemExit):
        smoke.report_divergences("a vs b", got, ref, scored, scored,
                                 require=True)
    assert "request 1 parts at new token 1" in capsys.readouterr().out


def test_four_chip_phase(forced_devices):
    r = forced_devices(f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              {os.path.join(ROOT, "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.phase_four_chips(smoke.CompileClock(), smoke=True, lens={LENS!r},
                       sizes={OPS!r})
print("OK")
""", n=4, timeout=900)
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr
    assert "decode state on 4 devices" in r.stdout
    for slots in (8, 2):
        assert (f"ShardedEngine(data=4) vs ContinuousEngine(slots={slots}): "
                f"same tokens for {len(LENS)} of {len(LENS)} requests"
                in r.stdout), r.stdout
