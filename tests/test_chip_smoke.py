"""CPU rehearsal of chip_smoke.py at a tiny size (rehearsals 1 and 2 of the
on-chip-measurement guide): the phase functions run with a small config on
the virtual CPU devices. The script itself has no branch that passes
without a chip — that is asserted here too."""
import os
import subprocess
import sys

import pytest

from paddle_tpu.distributed import mesh as mesh_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TINY = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=512,
            dtype="bfloat16")


@pytest.fixture()
def programs_dir(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(chip_smoke, "IR_DIR", str(tmp_path / "ir"))
    chip_smoke.dump_programs()
    yield
    jax.config.update("jax_dump_ir_to", None)


def test_train_then_serve_phases(programs_dir):
    meter = chip_smoke.CompileMeter()
    (model, out), rec = meter.phase("train", chip_smoke.train_phase, TINY,
                                    2, 128, 5)
    assert out["steps"] == 5 and len(out["losses"]) == 5
    assert out["losses"][-1] < out["losses"][0]
    assert 0 < rec["xla_compile_s"] < rec["wall_s"]
    # on the CPU the kernels run interpreted: no compiled kernel to find
    assert out["kernel_in_step"] is False

    lens, budgets = (40, 64, 100, 130), (8, 4, 8, 6)
    served = chip_smoke.serve_phase(model, lens, budgets)
    assert served["returned"] == list(budgets)
    assert served["worst_gap"] <= chip_smoke.SERVE_BAND


def test_serve_check_refuses_garbage(programs_dir, monkeypatch):
    """The teacher-forced check fails a decoder whose tokens are not the
    model's: serve() answers with a constant token."""
    from paddle_tpu.models.paged_decode import PagedDecoder
    model, _ = chip_smoke.train_phase(TINY, 2, 128, 2)
    monkeypatch.setattr(
        PagedDecoder, "serve",
        lambda self, reqs, **kw: {rid: [7] * b for rid, _, b in reqs})
    with pytest.raises(AssertionError, match="below the reference"):
        chip_smoke.serve_phase(model, (40, 64), (8, 8))


def test_sharded_train_phase_on_the_virtual_mesh(programs_dir):
    """dp x mp over the eight virtual devices of conftest.py (the chip run
    is dp2 x mp2 over four)."""
    import jax
    saved = mesh_mod._global_mesh[0]
    try:
        mesh_mod._global_mesh[0] = None
        _alive, out = chip_smoke.sharded_train_phase(TINY, 8, 128, 3,
                                                     dp=4, mp=2)
    finally:
        mesh_mod._global_mesh[0] = saved
    assert out["weight_on_devices"] == [d.id for d in jax.devices()]
    assert out["max_loss_diff"] <= chip_smoke.SHARDED_LOSS_BAND


def test_script_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for args in ([], ["--chips", "4"]):
        r = subprocess.run([sys.executable, "chip_smoke.py", *args],
                           cwd=ROOT, env=env, capture_output=True,
                           text=True, timeout=120)
        assert r.returncode != 0
        assert r.stdout == ""
