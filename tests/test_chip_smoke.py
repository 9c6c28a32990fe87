"""`chip_smoke.py` rehearsed on the CPU at smoke size.

The script itself refuses to run without a TPU; these tests drive its
phases with the reduced qwen2-0.5b config, with the Pallas kernels in
interpret mode, so a later change that breaks the chip smoke fails here
first.  The mesh phase runs in a subprocess on 4 forced host devices.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.registry import get_smoke_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(capsys):
    """No TPU: non-zero exit and no result line."""
    assert _load().main([]) == 2
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert json.loads(out.split("device: ", 1)[1])["platform"] == "cpu"


def test_phases_at_smoke_size(tmp_path):
    """Train (flash, interpret) -> compiled local step -> flash vs xla
    gradients -> serve from the checkpoint, each phase's own checks."""
    cs = _load()
    cfg = get_smoke_config("qwen2-0.5b")
    ckpt = str(tmp_path / "ckpt")
    out = cs.train_phase(cfg, ckpt, SEQ, log=lambda *a: None)
    assert "HloModule" in cs.local_step_hlo(out, SEQ)
    err = cs.grad_phase(cfg, out["avg_params"], SEQ, log=lambda *a: None)
    assert err["bf16"]["grad_rel_l2_err"] <= cs.GRAD_RTOL
    assert err["f32"]["grad_rel_l2_err"] <= cs.F32_GRAD_RTOL
    assert err["f32"]["worst_leaf_rel_l2_err"] <= cs.F32_LEAF_RTOL
    res = cs.serve_phase(cfg, ckpt, log=lambda *a: None)
    assert res["decode_rel_err"] <= cs.DECODE_RTOL


def test_grad_check_catches_a_wrong_dk(monkeypatch):
    """A 10% error in one KV head's dk, planted in the flash backward,
    fails the flash-vs-xla gradient check."""
    import jax
    from repro.kernels import flash_attention as fa_mod
    from repro.models import model as model_mod
    cs = _load()
    cfg = get_smoke_config("qwen2-0.5b")
    params = model_mod.init_model(jax.random.PRNGKey(0), cfg)
    sound = fa_mod.flash_attention_bwd

    def wrong_dk(*args, **kw):
        dq, dk, dv = sound(*args, **kw)
        return dq, dk.at[:, :, 0].multiply(1.1), dv

    monkeypatch.setattr(fa_mod, "flash_attention_bwd", wrong_dk)
    with pytest.raises(RuntimeError, match="f32: .* differ"):
        cs.grad_phase(cfg, params, SEQ, log=lambda *a: None)


@pytest.mark.subproc
def test_mesh_phase_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    script = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke as cs
        from repro.configs.registry import get_smoke_config
        res = cs.mesh_phase(get_smoke_config("qwen2-0.5b"), {SEQ},
                            log=lambda *a: None)
        print("RESULT", json.dumps(res))
    """)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.split("RESULT ", 1)[1])
    cs = _load()
    assert res["hub_event_rel_l2_err"] <= cs.MESH_EVENT_RTOL
    # on the CPU the grouped collectives mix bit for bit like vmap, and the
    # event without its hub roll is far outside the mixing bound
    assert res["mix_bit_identical"]
    assert res["mix_without_hub_roll_rel_l2_err"] > 10 * cs.MESH_MIX_RTOL
