"""Fault-tolerance subsystem (ISSUE 11): persistent AOT compile cache,
hardened checkpoint commit protocol, resharding restore matrix, watchdog
store-retry + peer-death naming.

The multi-process end-to-end face (SIGKILL mid-step, restart, resume,
loss parity) lives in tools/preempt_drill.py (run_ci.sh preempt tier);
these are the tier-1 invariants each leg must hold on its own.
"""
import glob
import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.distributed.resilience import (CheckpointManager,
                                               compile_cache as cc)
from paddle_tpu.distributed.checkpoint import (
    save_state_dict, load_state_dict, wait_async_save, drain_async_saves,
    validate_checkpoint, is_committed, CheckpointCorruptionError,
    MANIFEST_NAME)
import importlib

# the submodule (the package re-exports the function under the same name)
save_mod = importlib.import_module(
    "paddle_tpu.distributed.checkpoint.save_state_dict")


@pytest.fixture
def cache_dir(tmp_path):
    d = str(tmp_path / "ptcc")
    cc.reset_stats()
    set_flags({"compile_cache_dir": d})
    yield d
    set_flags({"compile_cache_dir": ""})
    cc.reset_stats()


def _corrupt_one(pattern):
    path = sorted(glob.glob(pattern))[0]
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(raw))
    return path


# -- compile cache -----------------------------------------------------------
class TestCompileCache:
    def test_miss_store_hit_roundtrip(self, cache_dir):
        f = jax.jit(lambda x: x @ x.T + 1.0)
        c1, i1 = cc.get_or_compile(f.lower(jnp.ones((8, 8))), tag="t")
        assert i1["cache"] == "miss"
        # a FRESH lowering of the same program must hit (the restart
        # path: nothing in-memory survives, only the entry file)
        c2, i2 = cc.get_or_compile(
            jax.jit(lambda x: x @ x.T + 1.0).lower(jnp.ones((8, 8))),
            tag="t")
        assert i2["cache"] == "hit" and i2["key"] == i1["key"]
        np.testing.assert_allclose(np.asarray(c1(jnp.ones((8, 8)))),
                                   np.asarray(c2(jnp.ones((8, 8)))))
        st = cc.stats()
        assert st["hits"] == 1 and st["misses"] == 1 and st["stores"] == 1
        assert st["bytes_written"] > 0 and st["bytes_read"] > 0

    @pytest.mark.parametrize("n_devices", [2, 8])
    def test_sharded_executable_reloads_on_its_own_devices(self, cache_dir,
                                                           n_devices):
        """An executable compiled for a sub-mesh reloads onto those
        devices, in their order — not onto every device of the backend."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        devs = jax.devices()[::-1][:n_devices]      # not the default order
        sh = NamedSharding(Mesh(np.array(devs), ("x",)), P("x"))
        x = jax.device_put(jnp.arange(16.0).reshape(8, 2), sh)

        def lowered():
            return jax.jit(lambda a: a * 2.0 + 1.0, in_shardings=sh,
                           out_shardings=sh).lower(x)

        _, i1 = cc.get_or_compile(lowered(), tag="t")
        c2, i2 = cc.get_or_compile(lowered(), tag="t")
        assert (i1["cache"], i2["cache"]) == ("miss", "hit")
        out = c2(x)
        assert out.sharding.is_equivalent_to(sh, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2 + 1)

    def test_corrupt_entry_recompiles_never_crashes(self, cache_dir):
        low = jax.jit(lambda x: x * 3.0).lower(jnp.ones((4,)))
        cc.get_or_compile(low, tag="t")
        _corrupt_one(os.path.join(cache_dir, "*.ptcc"))
        c, info = cc.get_or_compile(
            jax.jit(lambda x: x * 3.0).lower(jnp.ones((4,))), tag="t")
        assert info["cache"] == "miss"
        assert cc.stats()["corrupt"] == 1
        np.testing.assert_allclose(np.asarray(c(jnp.ones((4,)))), 3.0)
        # the bad entry was healed by the re-store: next process hits
        _, info3 = cc.get_or_compile(
            jax.jit(lambda x: x * 3.0).lower(jnp.ones((4,))), tag="t")
        assert info3["cache"] == "hit"

    def test_truncated_entry_is_corrupt(self, cache_dir):
        cc.get_or_compile(jax.jit(lambda x: x + 1).lower(
            jnp.ones((4,))), tag="t")
        path = sorted(glob.glob(os.path.join(cache_dir, "*.ptcc")))[0]
        raw = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(raw[:40])
        assert cc.load(cc.cache_key(jax.jit(lambda x: x + 1).lower(
            jnp.ones((4,))), tag="t")) is None
        assert cc.stats()["corrupt"] == 1

    def test_key_separates_shapes_and_tags(self, cache_dir):
        f = jax.jit(lambda x: x + 1)
        k1 = cc.cache_key(f.lower(jnp.ones((4,))), tag="a")
        k2 = cc.cache_key(f.lower(jnp.ones((8,))), tag="a")
        k3 = cc.cache_key(f.lower(jnp.ones((4,))), tag="b")
        assert len({k1, k2, k3}) == 3

    def test_disabled_is_noop(self, tmp_path):
        set_flags({"compile_cache_dir": ""})
        cc.reset_stats()
        _, info = cc.get_or_compile(
            jax.jit(lambda x: x + 1).lower(jnp.ones((4,))), tag="t")
        assert info["cache"] == "off"
        assert cc.stats() == {k: 0 for k in cc.stats()}

    @staticmethod
    def _train(steps=3):
        pt.seed(3)
        m = pt.nn.Sequential(pt.nn.Linear(6, 8), pt.nn.Tanh(),
                             pt.nn.Linear(8, 1))
        opt = pt.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=m.parameters())
        step = pt.jit.TrainStep(
            m, lambda o, t: pt.nn.functional.mse_loss(o, t), opt)
        rng = np.random.default_rng(0)
        out = []
        for _ in range(steps):
            x = pt.to_tensor(
                rng.standard_normal((4, 6)).astype("float32"))
            y = pt.to_tensor(np.zeros((4, 1), "float32"))
            out.append(float(step((x,), (y,))))
        return out, step

    @staticmethod
    def _jax_cache_events(fn):
        """fn()'s result and what JAX's persistent cache said meanwhile."""
        events = []

        def listen(event, **_):
            if event.startswith("/jax/compilation_cache/cache_"):
                events.append(event.rsplit("/", 1)[1])

        jax.monitoring.register_event_listener(listen)
        try:
            return fn(), events
        finally:
            jax.monitoring.unregister_event_listener(listen)

    def test_trainstep_warm_restart_parity(self, cache_dir):
        """The restart contract end to end: a second TrainStep over the
        same program finds BOTH its signatures on disk, the copies
        telemetry reads (the counters) and the executables that run
        (JAX's cache under the same flag), and walks the identical loss
        trajectory."""
        import paddle_tpu.observability as obs

        obs.enable()
        try:
            l1, _ = self._train()
            st1 = cc.stats()
            (l2, _), events = self._jax_cache_events(self._train)
            st2 = cc.stats()
        finally:
            obs.disable()
        assert st1["misses"] == 2 and st1["hits"] == 0, st1
        assert st2["hits"] == 2 and st2["misses"] == 2, st2
        assert events.count("cache_hits") >= 2, events
        assert "cache_misses" not in events
        np.testing.assert_allclose(l1, l2, rtol=1e-6)
        assert self._train(0)[1].compile_cache_last is None

    @staticmethod
    def _serve():
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.paged_decode import PagedDecoder
        pt.seed(5)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=97, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=64,
            use_flash_attention=False))
        model.eval()
        dec = PagedDecoder(model, max_len=32, block_size=16, max_slots=2,
                           num_blocks=9)
        return dec.serve([(0, [3, 1, 4, 1, 5], 4), (1, [9, 2, 6], 4)],
                         chunk=2), dec

    @pytest.mark.parametrize("entry", ["_train", "_serve"])
    def test_flag_alone_serves_the_programs_that_run(self, cache_dir,
                                                     entry):
        """With no telemetry the one flag still spares a restart its
        compiles: the jitted calls (two train-step signatures; a prefill
        bucket and a chunk) are retrieved from `<dir>/xla`, and emptying
        the flag gives JAX's cache settings back."""
        prior = dict(cc._JAX_PRIOR)
        assert jax.config.jax_compilation_cache_dir == (
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(cache_dir, "xla"))
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        run = getattr(self, entry)
        first, _ = run()
        (again, engine), events = self._jax_cache_events(run)
        assert events.count("cache_hits") >= 2, events
        assert "cache_misses" not in events
        assert first == again and engine._analysed == {}
        assert cc.stats() == {k: 0 for k in cc.stats()}
        assert not glob.glob(os.path.join(cache_dir, "*.ptcc"))
        set_flags({"compile_cache_dir": ""})
        assert prior and not cc._JAX_PRIOR
        assert {k: getattr(jax.config, k) for k in prior} == prior


# -- checkpoint commit protocol ----------------------------------------------
class TestCommitProtocol:
    def _save(self, tmp_path, value=1.0):
        d = str(tmp_path / "ckpt")
        save_state_dict(
            {"w": pt.to_tensor(np.full((4, 4), value, "float32")),
             "step": pt.to_tensor(np.asarray([7], "int32"))}, d)
        return d

    def test_commit_artifacts(self, tmp_path):
        d = self._save(tmp_path)
        assert os.path.exists(os.path.join(d, MANIFEST_NAME))
        assert is_committed(d)
        meta = validate_checkpoint(d)
        assert set(meta.state_dict_metadata) == {"w", "step"}
        doc = json.load(open(os.path.join(d, MANIFEST_NAME)))
        assert doc["schema"] == "paddle_tpu.ckpt/1"
        for integ in doc["files"].values():
            assert len(integ["sha256"]) == 64 and integ["bytes"] > 0
        for rows in doc["tensors"].values():
            assert all(isinstance(r["crc32"], int) for r in rows)

    def test_flipped_byte_is_rejected_cleanly(self, tmp_path):
        d = self._save(tmp_path)
        bad = _corrupt_one(os.path.join(d, "*.distcp"))
        assert not is_committed(d)
        target = {"w": pt.to_tensor(np.zeros((4, 4), "float32"))}
        with pytest.raises(CheckpointCorruptionError) as ei:
            load_state_dict(target, d)
        assert os.path.basename(bad) in str(ei.value)
        # the target was never touched — no NaNs, no partial restore
        np.testing.assert_array_equal(target["w"].numpy(), 0.0)

    def test_torn_manifest_rejected(self, tmp_path):
        d = self._save(tmp_path)
        mpath = os.path.join(d, MANIFEST_NAME)
        raw = open(mpath).read()
        with open(mpath, "w") as f:
            f.write(raw[:len(raw) // 2])
        assert not is_committed(d)
        with pytest.raises(CheckpointCorruptionError):
            validate_checkpoint(d)

    def test_missing_data_file_is_torn(self, tmp_path):
        d = self._save(tmp_path)
        os.unlink(sorted(glob.glob(os.path.join(d, "*.distcp")))[0])
        assert not is_committed(d)

    def test_shard_crc_catches_manifest_drift(self, tmp_path):
        d = self._save(tmp_path)
        mpath = os.path.join(d, MANIFEST_NAME)
        doc = json.load(open(mpath))
        doc["tensors"]["w"][0]["crc32"] ^= 0xFF
        with open(mpath, "w") as f:
            json.dump(doc, f)
        with pytest.raises(CheckpointCorruptionError) as ei:
            load_state_dict(
                {"w": pt.to_tensor(np.zeros((4, 4), "float32"))}, d)
        assert "crc32" in str(ei.value)

    def test_malformed_manifest_is_torn_not_keyerror(self, tmp_path):
        """A parsable manifest with a malformed row (missing field,
        wrong type) must classify as torn — a raw KeyError escaping
        from_manifest would crash latest_committed/restore/prune on
        the restart path instead of falling back."""
        d = self._save(tmp_path)
        mpath = os.path.join(d, MANIFEST_NAME)
        doc = json.load(open(mpath))
        doc["tensors"]["w"][0]["oefset"] = \
            doc["tensors"]["w"][0].pop("offset")
        with open(mpath, "w") as f:
            json.dump(doc, f)
        assert is_committed(d) is False
        with pytest.raises(CheckpointCorruptionError, match="malformed"):
            validate_checkpoint(d)

    def test_stale_tmp_files_ignored(self, tmp_path):
        d = self._save(tmp_path)
        open(os.path.join(d, "0_0.dead.distcp.tmp.999"), "wb").write(
            b"garbage")
        assert is_committed(d)
        tgt = {"w": pt.to_tensor(np.zeros((4, 4), "float32"))}
        load_state_dict(tgt, d)
        np.testing.assert_array_equal(tgt["w"].numpy(), 1.0)

    def test_resave_gcs_stale_generations(self, tmp_path):
        d = self._save(tmp_path, value=1.0)
        first = set(glob.glob(os.path.join(d, "*.distcp")))
        self._save(tmp_path, value=2.0)
        second = set(glob.glob(os.path.join(d, "*.distcp")))
        assert not (first & second), "old generation not GC'd"
        tgt = {"w": pt.to_tensor(np.zeros((4, 4), "float32"))}
        load_state_dict(tgt, d)
        np.testing.assert_array_equal(tgt["w"].numpy(), 2.0)

    def test_load_reads_through_manifest_not_glob(self, tmp_path):
        """An unreferenced alien .distcp in the directory must not be
        read (the old glob loader would have merged it)."""
        d = self._save(tmp_path)
        import pickle
        with open(os.path.join(d, "9_9.alien.distcp"), "wb") as f:
            pickle.dump({("w", (0, 0)): np.full((4, 4), 99.0,
                                                np.float32)}, f)
        tgt = {"w": pt.to_tensor(np.zeros((4, 4), "float32"))}
        load_state_dict(tgt, d)
        np.testing.assert_array_equal(tgt["w"].numpy(), 1.0)


# -- async save hardening ----------------------------------------------------
class TestAsyncHardening:
    def test_write_retries_transient_failures(self, tmp_path,
                                              monkeypatch):
        calls = {"n": 0}
        real = os.replace

        def flaky(src, dst):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise OSError("transient fs hiccup")
            return real(src, dst)

        monkeypatch.setattr(save_mod.os, "replace", flaky)
        monkeypatch.setattr(save_mod, "_BACKOFF_S", 0.001)
        d = str(tmp_path / "ckpt")
        save_state_dict({"w": pt.to_tensor(np.ones(4, "float32"))}, d)
        assert calls["n"] >= 3
        assert is_committed(d)

    def test_persistent_write_failure_raises(self, tmp_path,
                                             monkeypatch):
        def always(src, dst):
            raise OSError("disk on fire")

        monkeypatch.setattr(save_mod.os, "replace", always)
        monkeypatch.setattr(save_mod, "_BACKOFF_S", 0.001)
        with pytest.raises(OSError):
            save_state_dict({"w": pt.to_tensor(np.ones(4, "float32"))},
                            str(tmp_path / "ckpt"))

    def test_async_failure_surfaced_by_wait(self, tmp_path,
                                            monkeypatch):
        monkeypatch.setattr(save_mod, "_BACKOFF_S", 0.001)
        d = str(tmp_path / "ckpt")
        t = save_state_dict({"w": pt.to_tensor(np.ones(4, "float32"))},
                            d, async_save=True)
        # sabotage the manifest write AFTER the thread is racing
        assert t is not None
        wait_async_save()          # clean one first
        monkeypatch.setattr(save_mod.os, "replace",
                            lambda s, dd: (_ for _ in ()).throw(
                                OSError("boom")))
        save_state_dict({"w": pt.to_tensor(np.ones(4, "float32"))},
                        d, async_save=True)
        with pytest.raises(RuntimeError, match="async checkpoint"):
            wait_async_save()

    def test_drain_is_nonraising_and_bounded(self, tmp_path):
        d = str(tmp_path / "ckpt")
        save_state_dict({"w": pt.to_tensor(np.ones(4, "float32"))}, d,
                        async_save=True)
        assert drain_async_saves(timeout_s=30.0) is True
        assert not save_mod._PENDING
        assert is_committed(d)
        # atexit hook armed by the first async save
        assert save_mod._ATEXIT[0]

    def test_sigterm_path_drains_checkpoints(self, tmp_path):
        """flight_recorder's signal path drains in-flight writers so a
        preempted process commits its last save."""
        from paddle_tpu.observability import flight_recorder
        gate = threading.Event()
        d = str(tmp_path / "ckpt")
        real_write = save_mod._atomic_write

        def slow_write(path, data, what):
            gate.wait(5.0)
            return real_write(path, data, what)

        save_mod._atomic_write = slow_write
        try:
            save_state_dict({"w": pt.to_tensor(np.ones(4, "float32"))},
                            d, async_save=True)
            assert save_mod._PENDING
            gate.set()
            flight_recorder._drain_checkpoints()
            assert not save_mod._PENDING
        finally:
            save_mod._atomic_write = real_write
        assert is_committed(d)

    def test_async_snapshot_isolated_from_mutation(self, tmp_path):
        w = pt.to_tensor(np.arange(16, dtype="float32").reshape(4, 4))
        d = str(tmp_path / "ckpt")
        save_state_dict({"w": w}, d, async_save=True)
        with pt.no_grad():
            w.set_value(pt.to_tensor(np.zeros((4, 4), "float32")))
        wait_async_save()
        tgt = {"w": pt.to_tensor(np.zeros((4, 4), "float32"))}
        load_state_dict(tgt, d)
        np.testing.assert_array_equal(
            tgt["w"].numpy(),
            np.arange(16, dtype="float32").reshape(4, 4))


# -- resharding restore matrix -----------------------------------------------
def _dp4_checkpoint(tmp_path):
    """Save a dp4-sharded state (params + Adam moments + i32 step) from
    a 4-device ('dp',) sub-mesh."""
    mesh4 = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
    w = np.arange(64, dtype=np.float32).reshape(8, 8)
    m1 = w * 0.1
    m2 = w * 0.01 + 1.0
    sd = {}
    for key, host in (("w", w), ("w::moment1", m1), ("w::moment2", m2)):
        t = pt.to_tensor(host)
        t._data = jax.device_put(t._data,
                                 NamedSharding(mesh4, P("dp", None)))
        sd[key] = t
    sd["step"] = pt.Tensor(jnp.asarray([5], jnp.int32),
                           stop_gradient=True)
    d = str(tmp_path / "dp4")
    save_state_dict(sd, d)
    return d, w, m1, m2


class TestReshardingMatrix:
    def test_dp4_to_dp2xmp2(self, tmp_path):
        d, w, m1, m2 = _dp4_checkpoint(tmp_path)
        meta = validate_checkpoint(d)
        assert len(meta.state_dict_metadata["w"]) == 4  # really sharded
        mesh22 = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                      ("dp", "mp"))
        tgt = {}
        for key in ("w", "w::moment1", "w::moment2"):
            t = pt.to_tensor(np.zeros((8, 8), "float32"))
            t._data = jax.device_put(
                t._data, NamedSharding(mesh22, P("dp", "mp")))
            tgt[key] = t
        tgt["step"] = pt.Tensor(jnp.zeros((1,), jnp.int32),
                                stop_gradient=True)
        load_state_dict(tgt, d)
        np.testing.assert_array_equal(tgt["w"].numpy(), w)
        np.testing.assert_array_equal(tgt["w::moment1"].numpy(), m1)
        np.testing.assert_array_equal(tgt["w::moment2"].numpy(), m2)
        assert str(tgt["w"]._data.sharding.spec) == str(P("dp", "mp"))
        assert int(np.asarray(tgt["step"]._data)[0]) == 5

    def test_dp4_to_dp1(self, tmp_path):
        d, w, m1, _ = _dp4_checkpoint(tmp_path)
        tgt = {"w": pt.to_tensor(np.zeros((8, 8), "float32")),
               "w::moment1": pt.to_tensor(np.zeros((8, 8), "float32")),
               "step": pt.Tensor(jnp.zeros((1,), jnp.int32),
                                 stop_gradient=True)}
        load_state_dict(tgt, d)
        np.testing.assert_array_equal(tgt["w"].numpy(), w)
        np.testing.assert_array_equal(tgt["w::moment1"].numpy(), m1)

    def test_i32_preserved_and_lint_clean(self, tmp_path):
        from paddle_tpu.analysis.hlo_lint import assert_tree_i32
        d, _, _, _ = _dp4_checkpoint(tmp_path)
        tgt = {"step": pt.Tensor(jnp.zeros((1,), jnp.int32),
                                 stop_gradient=True)}
        load_state_dict(tgt, d)
        assert tgt["step"]._data.dtype == jnp.int32
        # the restored step metadata enters traced code later: it must
        # already be i32 (the s64 trap class the linter enforces)
        assert_tree_i32({"step": tgt["step"]._data}, what="restored step")

    def test_corrupted_shard_never_becomes_nans(self, tmp_path):
        d, w, _, _ = _dp4_checkpoint(tmp_path)
        _corrupt_one(os.path.join(d, "*.distcp"))
        tgt = {"w": pt.to_tensor(np.zeros((8, 8), "float32"))}
        with pytest.raises(CheckpointCorruptionError):
            load_state_dict(tgt, d)
        assert np.isfinite(tgt["w"].numpy()).all()
        np.testing.assert_array_equal(tgt["w"].numpy(), 0.0)


# -- CheckpointManager -------------------------------------------------------
class TestCheckpointManager:
    def test_latest_committed_skips_torn(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=10)
        for step in (1, 2, 3):
            mgr.save({"w": pt.to_tensor(
                np.full((4,), float(step), "float32"))}, step)
        assert mgr.latest_committed()[0] == 3
        _corrupt_one(os.path.join(mgr.step_dir(3), "*.distcp"))
        assert mgr.latest_committed()[0] == 2
        tgt = {"w": pt.to_tensor(np.zeros((4,), "float32"))}
        assert mgr.restore(tgt) == 2
        np.testing.assert_array_equal(tgt["w"].numpy(), 2.0)

    def test_restore_none_when_nothing_committed(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.latest_committed() is None
        assert mgr.restore({"w": pt.to_tensor(
            np.zeros((4,), "float32"))}) is None

    def test_prune_keeps_newest_and_never_touches_torn(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for step in (1, 2, 3, 4):
            mgr.save({"w": pt.to_tensor(np.ones((4,), "float32"))},
                     step)
        steps = sorted(s for s, _ in mgr._step_dirs())
        assert steps == [3, 4], steps
        # torn dirs are NEVER pruned: cheaply indistinguishable from a
        # save in flight (and kill-window forensics) — newer AND older
        os.makedirs(mgr.step_dir(9))
        os.makedirs(mgr.step_dir(2))
        mgr.prune()
        assert os.path.isdir(mgr.step_dir(9))
        assert os.path.isdir(mgr.step_dir(2))

    def test_prune_never_evicts_last_restorable(self, tmp_path):
        """Corrupt-manifest-intact squatters filling the keep window
        must not get the last genuinely loadable checkpoint deleted:
        prune validates the kept set before any deletion and skips
        deletion when none of it restores."""
        mgr = CheckpointManager(str(tmp_path), keep=2)
        mgr.save({"w": pt.to_tensor(np.ones((4,), "float32"))}, 1)
        for step in (7, 8):       # two newer corrupt squatters
            save_state_dict({"w": pt.to_tensor(
                np.ones((4,), "float32"))}, mgr.step_dir(step))
            _corrupt_one(os.path.join(mgr.step_dir(step), "*.distcp"))
        mgr.prune()
        assert os.path.isdir(mgr.step_dir(1)), \
            "prune evicted the only restorable checkpoint"
        tgt = {"w": pt.to_tensor(np.zeros((4,), "float32"))}
        assert mgr.restore(tgt) == 1

    def test_prune_ignores_corrupt_squatter_and_inflight(self, tmp_path):
        """The drill's regression: a byte-corrupt checkpoint with an
        intact manifest NEWER than everything real must not cause
        prune to delete an in-flight (manifest-less) save dir."""
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for step in (1, 2, 3):
            mgr.save({"w": pt.to_tensor(np.ones((4,), "float32"))},
                     step)
        save_state_dict({"w": pt.to_tensor(np.ones((4,), "float32"))},
                        mgr.step_dir(11))
        _corrupt_one(os.path.join(mgr.step_dir(11), "*.distcp"))
        os.makedirs(mgr.step_dir(4))      # in-flight: no manifest yet
        mgr.prune()
        assert os.path.isdir(mgr.step_dir(4)), \
            "in-flight save dir was pruned"
        # restore still skips the corrupt squatter
        tgt = {"w": pt.to_tensor(np.zeros((4,), "float32"))}
        assert mgr.restore(tgt) == 3


# -- watchdog hardening ------------------------------------------------------
class _FlakyStore:
    def __init__(self, fail_times=0, dead=False):
        self.kv = {}
        self.fails_left = fail_times
        self.dead = dead
        self.calls = 0

    def _maybe_fail(self):
        self.calls += 1
        if self.dead:
            raise ConnectionError("store unreachable")
        if self.fails_left > 0:
            self.fails_left -= 1
            raise ConnectionError("transient")

    def set(self, k, v):
        self._maybe_fail()
        self.kv[k] = v.encode() if isinstance(v, str) else v

    def get(self, k):
        self._maybe_fail()
        return self.kv[k]

    def check(self, k):
        self._maybe_fail()
        return k in self.kv


class TestWatchdogHardening:
    def _mgr(self, store, world=2):
        from paddle_tpu.distributed.comm_watchdog import CommTaskManager
        m = CommTaskManager()
        m._store = store
        m._rank = 0
        m._world = world
        return m

    def test_transient_store_error_retried(self):
        store = _FlakyStore(fail_times=2)
        m = self._mgr(store)
        out = m._store_op("probe", lambda: store.set("k", "v"))
        assert m.store_retry_count == 2
        assert m.store_failure_count == 0
        assert store.kv["k"] == b"v"

    def test_persistent_store_error_counted_not_fatal(self):
        store = _FlakyStore(dead=True)
        m = self._mgr(store)
        assert m._store_op("probe", lambda: store.get("k")) is None
        assert m.store_failure_count == 1
        # and a flaky store never fabricates peer state
        m._check_peer(1, time.monotonic())
        assert m.dead_peers == [] and m.peer_errors == []

    def test_peer_death_names_rank_in_flight_dump(self, tmp_path):
        from paddle_tpu.observability import flight_recorder
        set_flags({"comm_watchdog_peer_dead_s": 0.2})
        try:
            store = _FlakyStore()
            store.kv["watchdog/heartbeat/1"] = b"111"
            m = self._mgr(store)
            fr = str(tmp_path / "flight.json")
            flight_recorder.arm(fr, install_signals=False)
            try:
                now = time.monotonic()
                m._check_peer(1, now)            # first sighting
                assert m.dead_peers == []
                m._check_peer(1, now + 0.1)      # fresh enough
                assert m.dead_peers == []
                m._check_peer(1, now + 1.0)      # stale -> dead, NAMED
                assert m.dead_peers == [1]
                doc = json.load(open(fr))
                assert doc["reason"] == "watchdog_peer_death:rank1"
                assert doc["extra"]["dead_rank"] == 1
                assert doc["extra"]["world_size"] == 2
                assert doc["extra"]["last_heartbeat_age_s"] >= 0.2
            finally:
                flight_recorder.disarm()
        finally:
            set_flags({"comm_watchdog_peer_dead_s": 0.0})

    def test_store_outage_cannot_fabricate_death(self):
        """A store that dies AFTER a peer was sighted must not turn
        heartbeat-read failures into a peer death — only a LIVE store
        serving an unchanging heartbeat may (the death judgment runs
        only on ticks whose read succeeded)."""
        set_flags({"comm_watchdog_peer_dead_s": 0.2})
        try:
            store = _FlakyStore()
            store.kv["watchdog/heartbeat/1"] = b"111"
            m = self._mgr(store)
            now = time.monotonic()
            m._check_peer(1, now)            # healthy sighting
            store.dead = True                # store outage begins
            m._check_peer(1, now + 10.0)     # way past the threshold
            assert m.dead_peers == []
            assert m.store_failure_count > 0
            store.dead = False               # store recovers, peer alive
            store.kv["watchdog/heartbeat/1"] = b"222"
            m._check_peer(1, now + 10.5)
            assert m.dead_peers == []
        finally:
            set_flags({"comm_watchdog_peer_dead_s": 0.0})

    def test_heartbeat_progress_resets_staleness(self):
        set_flags({"comm_watchdog_peer_dead_s": 0.5})
        try:
            store = _FlakyStore()
            store.kv["watchdog/heartbeat/1"] = b"111"
            m = self._mgr(store)
            now = time.monotonic()
            m._check_peer(1, now)
            store.kv["watchdog/heartbeat/1"] = b"222"  # peer ticked
            m._check_peer(1, now + 1.0)
            assert m.dead_peers == []
        finally:
            set_flags({"comm_watchdog_peer_dead_s": 0.0})

    def test_peer_death_disabled_by_default(self):
        store = _FlakyStore()
        store.kv["watchdog/heartbeat/1"] = b"111"
        m = self._mgr(store)
        now = time.monotonic()
        m._check_peer(1, now)
        m._check_peer(1, now + 3600.0)
        assert m.dead_peers == []

    def test_peer_error_propagation_still_works(self):
        store = _FlakyStore()
        store.kv["watchdog/error/1"] = b"rank 1 exploded"
        m = self._mgr(store)
        m._check_peer(1, time.monotonic())
        assert m.peer_errors == [(1, "rank 1 exploded")]
