"""One way to build and call each program (ISSUE 33): telemetry observes
the program that runs and never swaps it.

- The same run with `observability.enable()` and without gives the same
  tokens (losses) from the same jitted callables (their cache sizes and
  the bucket dictionaries agree), and only the telemetry-on run has an
  analysis record for the program.
- In `serving/batcher.py` and `jit/train_step.py` no call's callee
  depends on `telemetry` (read off the source).
- The step ledgers' `compile` bucket is what the compile listener heard:
  above zero on a first call, zero on the third; the listener counts
  with nothing recording.
"""
import ast
import json
import pathlib

import numpy as np
import pytest

import jax

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs
from paddle_tpu.observability import memory_profile, roofline, tracing

ROOT = pathlib.Path(pt.__file__).resolve().parent


@pytest.fixture
def plain():
    """Telemetry off and nothing recorded before and after."""
    assert not obs.enabled()
    obs.registry().reset()
    memory_profile.reset()
    roofline.reset()
    tracing.clear()      # whatever an earlier file's tests left in the ring
    yield
    obs.disable()
    obs.set_jsonl_path(None)
    obs.registry().reset()
    memory_profile.reset()
    roofline.reset()


# -- the same program, observed or not -----------------------------------------
def _train_step():
    pt.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 3))
    opt = pt.optimizer.AdamW(learning_rate=0.05,
                             parameters=net.parameters())
    step = pt.jit.TrainStep(net, lambda o, l: ((o - l) ** 2).mean(), opt)
    rng = np.random.default_rng(0)
    losses = [float(step(pt.to_tensor(rng.standard_normal((4, 4),
                                                           np.float32)),
                         pt.to_tensor(rng.standard_normal((4, 3),
                                                          np.float32))))
              for _ in range(3)]
    labels = [f"train_step:{label}"
              for label in step.analysed_executables()]
    return losses, {"step": step._jitted._cache_size()}, step, labels


def _llama_decoder(**kw):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.paged_decode import PagedDecoder
    pt.seed(5)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, use_flash_attention=False))
    model.eval()
    return PagedDecoder(model, max_len=32, block_size=16, max_slots=2,
                        num_blocks=9, **kw)


def _hybrid_decoder():
    from chipbench.adapters import nemotron_h as adapter
    from chipbench.reference import nemotron_h as ref
    from paddle_tpu.models.paged_decode import PagedDecoder
    import test_nemotron_h as tiny
    weights = {k: v.astype(tiny.F32)
               for k, v in ref.make_weights(tiny.CFG, tiny.SEED).items()}
    return PagedDecoder(adapter.build_model(tiny.CFG, weights), max_len=64,
                        block_size=8, num_blocks=33, max_slots=2)


def _programs(dec):
    """How many executables each program the loop calls holds."""
    sizes = {"chunk": dec._paged_chunk_state_jit._cache_size()}
    if dec._spec_verify_jit is not None:
        sizes["verify"] = dec._spec_verify_jit._cache_size()
    for name, cache in (("prefill", dec._prefill_cache),
                        ("warmfill", dec._warm_cache)):
        sizes[name] = {b: fn._cache_size() for b, fn in cache.items()}
    return sizes


def _serve(make, vocab=97, **kw):
    def run():
        dec = make()
        rng = np.random.default_rng(3)
        reqs = [(i, [int(t) for t in rng.integers(0, vocab, 4 + i)], 4 + i)
                for i in range(3)]
        out = dec.serve(reqs, chunk=2, **kw)
        if dec.prefix_cache is not None:
            # the same prompts again: the warm program's cached side
            out = (out, dec.serve(reqs, chunk=2, **kw))
        return out, _programs(dec), dec, None
    return run


CASES = {
    "train_step": (_train_step, None),
    "cold_prefill": (_serve(_llama_decoder), "serve:prefill_b16"),
    "warm_prefill": (_serve(lambda: _llama_decoder(prefix_cache=True)),
                     "serve:warmfill_b16"),
    "state_chunk": (_serve(_llama_decoder), "serve:chunkst_n2"),
    "spec_verify": (_serve(_llama_decoder,
                           spec_decode={"k": 2, "draft": "ngram"}),
                    "serve:spec_k2"),
    "hybrid_chunk": (_serve(_hybrid_decoder, vocab=256),
                     "serve:chunkst_n2"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_telemetry_observes_the_same_program(plain, case):
    run, label = CASES[case]
    want, want_programs, engine, _ = run()
    assert engine._analysed == {}          # nothing analysed unobserved
    assert memory_profile.ledgers() == {} and roofline.records() == {}

    obs.enable()
    got, got_programs, engine, labels = run()
    obs.disable()
    assert got == want
    assert got_programs == want_programs
    if case == "hybrid_chunk":
        from paddle_tpu.models.nemotron_h import HybridPagedDecoder
        assert isinstance(engine, HybridPagedDecoder)
    # the analysis record of the case's program, and only under telemetry
    labels = labels if label is None else [label]
    assert labels
    for key in labels:
        ledger = memory_profile.ledgers()[key]
        assert memory_profile.verify_ledger(ledger) == []
        assert ledger["peak_bytes"] > 0
        assert key in roofline.records()
    records = [r for r in engine._analysed.values() if r is not None]
    assert len(records) == len(engine._analysed) >= len(labels)
    for rec in records:
        # compiled to be read, with what the readers take from it
        assert rec["executable"].memory_analysis() is not None
        assert rec["cache"] == "off" and rec["flops"] >= 0
        assert rec["hbm"] is not None


# -- no callee depends on `telemetry` ------------------------------------------
def _mentions(node, name="telemetry"):
    return any(isinstance(n, ast.Name) and n.id == name
               for n in ast.walk(node))


def _has_call(nodes):
    return any(isinstance(n, ast.Call)
               for node in nodes for n in ast.walk(node))


def callees_chosen_by_telemetry(source):
    """Where `source` lets `telemetry` decide what is called: a
    conditional expression or an `if ... else` on it with a call on the
    side telemetry does not take, or a name bound under `if telemetry`
    that is called later. [(line, why)]."""
    tree = ast.parse(source)
    found = []
    bound = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.IfExp) and _mentions(node.test):
            if _has_call([node.orelse]):
                found.append((node.lineno, "a call only when "
                              "telemetry is off"))
        elif isinstance(node, ast.If) and _mentions(node.test):
            if _has_call(node.orelse):
                found.append((node.lineno, "a call only when "
                              "telemetry is off"))
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                        targets = (sub.targets if isinstance(sub, ast.Assign)
                                   else [sub.target])
                        for t in targets:
                            for n in ast.walk(t):
                                if isinstance(n, ast.Name):
                                    bound.setdefault(n.id, sub.lineno)
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in bound):
            found.append((node.lineno, f"calls `{node.func.id}`, bound "
                          f"under `if telemetry` at line "
                          f"{bound[node.func.id]}"))
    return found


@pytest.mark.parametrize("module", ["serving/batcher.py",
                                    "jit/train_step.py"])
def test_no_callee_depends_on_telemetry(module):
    source = (ROOT / module).read_text()
    assert "telemetry" in source
    assert callees_chosen_by_telemetry(source) == []


@pytest.mark.parametrize("fork", [
    # the three forms the serve loop and the train step had
    "out = fn(*args) if telemetry else eng._paged_chunk_state_jit(*args)",
    "if telemetry:\n    out = twin(sig, args)\nelse:\n    out = jitted(*args)",
    "if telemetry:\n    fn, built = eng.twin_of(k, args)\nout = fn(*args)",
])
def test_the_source_check_sees_a_fork(fork):
    assert callees_chosen_by_telemetry(fork)


# -- compile seconds come from the listener -----------------------------------
def _attributions(path, source):
    recs = [json.loads(line) for line in open(path)]
    return [r["attribution"] for r in recs
            if r.get("event") == "step_attribution"
            and r.get("source") == source]


def test_train_step_compile_bucket_is_what_the_listener_heard(plain,
                                                              tmp_path):
    path = str(tmp_path / "steps.jsonl")
    obs.enable()
    obs.set_jsonl_path(path)
    _train_step()
    obs.set_jsonl_path(None)
    first, _, third = _attributions(path, "train_step")
    assert first["compile"] > 0 and first["execute"] > 0
    assert third["compile"] == 0 and third["execute"] > 0
    count, total = obs.registry().histogram(
        "paddle_tpu_train_step_compile_seconds").value()
    # the first step, and the second (the accumulators materialize)
    assert count == 2 and total > 0


def test_serve_compile_bucket_is_what_the_listener_heard(plain, tmp_path):
    obs.enable()
    dec = _llama_decoder()
    reqs = [(i, [5, 6, 7, 8, 9][:3 + i], 5) for i in range(2)]
    compile_s = []
    for call in range(3):
        path = str(tmp_path / f"serve{call}.jsonl")
        obs.set_jsonl_path(path)
        dec.serve(reqs, chunk=2)
        obs.set_jsonl_path(None)
        attrs = _attributions(path, "serve")
        assert attrs and all(a["execute"] > 0 for a in attrs)
        compile_s.append([a["compile"] for a in attrs])
    assert max(compile_s[0]) > 0
    assert set(compile_s[2]) == {0}


def test_compile_seconds_count_with_nothing_recording(plain):
    assert not tracing.recording()

    @jax.jit
    def a_program_only_this_test_compiles(x):
        return x * 5 - 3

    x = jax.numpy.arange(6.0)
    before = tracing.compile_seconds()
    a_program_only_this_test_compiles(x).block_until_ready()
    heard = tracing.compile_seconds() - before
    assert heard > 0
    a_program_only_this_test_compiles(x).block_until_ready()
    assert tracing.compile_seconds() - before == heard
    assert tracing.tail() == []
