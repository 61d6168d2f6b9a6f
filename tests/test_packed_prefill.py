"""The hybrid engine's packed prefill: several prompts laid one after
another along the token axis of one program, each from a chunk boundary.

What a prompt gives in a pack is what it gives alone (first token, K and
V pages, SSM state rows, conv rows), whatever its neighbours hold; the
serve loop packs a staged scan's prompts and serves the same tokens; and
the programs this change must not move (the dense engine's, the LFM2
train step) lower to the text they lowered to at the parent commit.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.paged_decode import PagedDecoder


# -- programs outside the pack lower to the parent's text ----------------------------

def _dense_programs():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pt.seed(5)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, use_flash_attention=False,
        dtype="float32"))
    model.eval()
    dec = PagedDecoder(model, max_len=64, block_size=8, max_slots=4,
                       num_blocks=23)
    s, mb = dec.max_slots, dec.blocks_per_seq
    pools = dec.new_pools()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    flag = lambda *shape: jnp.zeros(shape, bool)
    yield "dense_chunk", dec._paged_chunk_state_jit.lower(
        dec._params, i32(s), i32(s), i32(s, mb), flag(s), i32(s), flag(s),
        *pools, 2, -1).as_text()
    yield "dense_prefill", dec._prefill_exec(16).lower(
        dec._params, i32(16), i32(), i32(mb), *pools).as_text()


def _lfm2_step():
    from paddle_tpu.models import lfm2
    model = lfm2.Lfm2ForCausalLM(lfm2.lfm2_tiny())
    crit = lfm2.Lfm2PretrainingCriterion()
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    step = pt.jit.TrainStep(model, lambda lg, lb: crit(lg, lb), opt)
    jitted, texts = step._jitted, []

    def lowered_then_called(*args):
        texts.append(jitted.lower(*args).as_text())
        return jitted(*args)
    step._jitted = lowered_then_called
    toks = np.random.default_rng(0).integers(0, 64, (2, 17))
    step((pt.to_tensor(toks[:, :-1], dtype="int64"),),
         (pt.to_tensor(toks[:, 1:], dtype="int64"),))
    yield "lfm2_train_step", texts[0]


PARENT = {
    "dense_chunk":
        "88fbf2462bb15ef9f315f54afe3ce86f67a53dd7b75dccdcf8721b9fe71cd11a",
    "dense_prefill":
        "d9fbbda47423b3e92fed36f5ac3287880927ff46f45cd115a902c1f8723349ad",
    "lfm2_train_step":
        "59a590bc0b890f1f38fe90651d0ddf4bbb3ea764361a155ccedb48525712570a",
}


@pytest.mark.parametrize("programs", [_dense_programs, _lfm2_step])
def test_programs_outside_the_pack_lower_to_the_parents_text(programs):
    """sha256 of the CPU lowering at the parent commit (no Mosaic body on
    the CPU, so no source location to strip): `conv_sequence`,
    `ssd_chunked`, `attention_sequence` and the serve loop changed, the
    dense engine's programs and the train step that shares `moe_route`
    and the sort did not. A PR that means to change one replaces its
    digest."""
    for name, text in programs():
        assert hashlib.sha256(text.encode()).hexdigest() == PARENT[name], name


# -- a prompt in a pack gives what it gives alone -------------------------------------

BLOCK, SLOTS = 8, 4
# shorter than a chunk; ends inside a chunk; fills its rows
LENGTHS = (5, 13, 16)
STARTS = (0, 8, 24)
TABLES = np.zeros((SLOTS, 8), np.int32)
TABLES[1, :1], TABLES[2, :2], TABLES[3, :2] = [5], [2, 7], [11, 3]
MEMBER_SLOTS = (1, 2, 3)


@pytest.fixture(scope="module")
def model():
    """A Mamba block behind the attention block, so that what attention
    saw shows in a state row and not only in the first token."""
    return nh.NemotronHForCausalLM(nh.nemotron_h_tiny(
        hybrid_override_pattern="ME*ME", experts_held=(4, 8)))


def _decoder(model, **kw):
    return PagedDecoder(model, max_len=64, block_size=BLOCK, num_blocks=33,
                        max_slots=SLOTS, **kw)


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in LENGTHS]


def _prefilled(dec, bucket, members):
    """One prefill program over `members` on fresh pools: what each
    member left behind, as numpy: (first token, its K pages, its V
    pages, its SSM state rows, its conv rows)."""
    head, tail = dec._prefill_inputs(bucket, members, TABLES, 0)
    enc, kpool, vpool, ssm, conv = dec._prefill_exec(bucket)(
        dec._params, *head, *dec.new_pools(), *tail)
    out = []
    for seg, (slot, prompt, _) in enumerate(members):
        blocks = TABLES[slot][:-(-len(prompt) // BLOCK)]
        pages = [np.asarray(pool)[:, blocks].reshape(
            pool.shape[0], -1, *pool.shape[3:])[:, :len(prompt)]
            for pool in (kpool, vpool)]
        out.append((dec.decode_first_token([enc], seg), *pages,
                    np.asarray(ssm)[:, slot], np.asarray(conv)[:, slot]))
    return out


def _pack(prompts):
    return [(slot, p, start)
            for slot, p, start in zip(MEMBER_SLOTS, prompts, STARTS)]


def _check_pack_against_alone(dec):
    prompts = _prompts()
    packed = _prefilled(dec, 64, _pack(prompts))
    for (slot, prompt, _), got in zip(_pack(prompts), packed):
        (want,) = _prefilled(dec, dec.prefill_bucket(len(prompt)),
                             [(slot, prompt, 0)])
        assert got[0] == want[0]
        for a, b in zip(got[1:], want[1:]):
            assert a.shape == b.shape
            assert np.abs(a - b).max() <= 2e-6 * max(1.0, np.abs(b).max())


def test_each_prompt_of_a_pack_gives_what_it_gives_alone(model):
    """First token, K and V pages, SSM state rows and conv rows of a
    prompt laid behind others in one program are those of its own
    program (float32 storage; the sums inside a prompt's chunks run in
    the same order, the masked attention row is longer)."""
    _check_pack_against_alone(_decoder(model))


def test_packs_are_filled_longest_first(model):
    """A padded row costs what a real one costs: a scan's prompts go
    first-fit into packs of `max_len` rows, the longest first, each from
    the chunk boundary behind the one before it, and a pack runs in the
    smallest bucket that holds it."""
    dec = _decoder(model)
    assert dec.prefill_buckets() == [8, 16, 32, 64]
    assert dec.prefill_packs([5, 13, 16, 30, 40, 3]) == [
        (64, [(4, 0), (2, 40), (0, 56)]), (64, [(3, 0), (1, 32), (5, 48)])]
    assert dec.prefill_packs([5, 13, 16]) == [(64, [(2, 0), (1, 16), (0, 32)])]
    assert dec.prefill_packs([7, 2]) == [(16, [(0, 0), (1, 8)])]
    assert dec.prefill_packs([64, 64]) == [(64, [(0, 0)]), (64, [(1, 0)])]
    assert dec.prefill_packs([3]) == [(8, [(0, 0)])]
    assert dec.prefill_packs([]) == []


@pytest.mark.parametrize("target", [0, 1, 2])
def test_a_neighbours_ids_change_nothing_bit_for_bit(model, target):
    dec = _decoder(model)
    prompts, others = _prompts(), _prompts(seed=9)
    changed = [p if k == target else q
               for k, (p, q) in enumerate(zip(prompts, others))]
    a = _prefilled(dec, 64, _pack(prompts))[target]
    b = _prefilled(dec, 64, _pack(changed))[target]
    assert a[0] == b[0]
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)


def _taps_cross(orig):
    def conv_sequence(xbc, w, b, starts, lens):
        out = orig(xbc, w, b, *nh.one_segment(xbc.shape[0]))[0]
        return out, orig(xbc, w, b, starts, lens)[1]
    return conv_sequence


def _carry_kept(orig):
    # no chunk opens a segment: the scan's carry is never reset
    return lambda *a: orig(*a[:7], a[7] + 1)


def _mask_causal_only(orig):
    return lambda cfg, p, u, starts, lens: orig(
        cfg, p, u, *nh.one_segment(u.shape[0]))


@pytest.mark.parametrize("name,fault", [
    ("conv_sequence", _taps_cross), ("ssd_chunked", _carry_kept),
    ("attention_sequence", _mask_causal_only)])
def test_a_planted_fault_fails_the_check(model, monkeypatch, name, fault):
    """A tap that crosses a segment's start, a carry that is not reset,
    a mask without the segment: each makes a packed prompt see its
    neighbour, and the comparison with the prompt alone says so."""
    monkeypatch.setattr(nh, name, fault(getattr(nh, name)))
    with pytest.raises(AssertionError):
        _check_pack_against_alone(_decoder(model))


# -- the serve loop packs a staged scan's prompts -------------------------------------

def _requests():
    rng = np.random.default_rng(4)
    shapes = [(5, 9), (12, 20), (8, 3), (17, 11), (3, 17), (9, 6), (30, 25),
              (21, 4), (2, 12)]
    return [(rid, rng.integers(0, 256, n).tolist(), budget)
            for rid, (n, budget) in enumerate(shapes)]


def _served(model, fault_plan=None, **kw):
    from paddle_tpu.observability import tracing
    from paddle_tpu.resilience import faults
    dec = _decoder(model, **kw)
    tracing.enable_tracing()
    try:
        tracing.drain()
        if fault_plan:
            faults.install_plan(fault_plan)
        out = dec.serve(_requests(), max_new_tokens=25, chunk=4,
                        max_restarts=4)
        spans = tracing.drain()
    finally:
        faults.clear()
        tracing.disable_tracing()
    return dec, out, spans


@pytest.fixture(scope="module")
def one_at_a_time(model):
    return _served(model)


def _metas(spans, name):
    return [s["meta"] for s in spans if s["name"] == name]


def test_a_staged_scan_is_packed_and_serves_the_same_tokens(
        model, one_at_a_time):
    alone, want, spans_alone = one_at_a_time
    dec, got, spans = _served(model, pipelined_admission=True)
    assert got == want
    admits = _metas(spans, "serve:admit")
    prefills = _metas(spans, "serve:prefill")
    assert len(admits) == len(_requests()) == alone.prefill_device_calls
    assert dec.prefill_device_calls == len(prefills) < len(admits)
    assert sum(m["prompts"] for m in prefills) == len(admits)
    assert sum(m["rows"] for m in prefills) \
        == sum(m["prompt_tokens"] for m in admits) \
        == dec.prefill_tokens_computed == alone.prefill_tokens_computed
    # one at a time, every program holds one prompt
    assert [m["prompts"] for m in _metas(spans_alone, "serve:prefill")] \
        == [1] * len(admits)
    # a pack's counts ride on its first admission, the others carry 0:
    # pairs sum to the per-prompt run's, and a pack touches an expert
    # once where its prompts alone touched it once each
    alone_admits = _metas(spans_alone, "serve:admit")
    for key in ("moe_pairs_here", "moe_pairs_all"):
        assert sum(m[key] for m in admits) \
            == sum(m[key] for m in alone_admits) > 0
    assert sum(m["moe_experts_touched"] for m in admits) \
        < sum(m["moe_experts_touched"] for m in alone_admits)
    assert sum(m["moe_pairs_here"] > 0 for m in admits) == len(prefills)
    # every bucket's program was made before the first admission
    assert sorted(dec._prefill_cache) == dec.prefill_buckets() \
        == [8, 16, 32, 64]
    assert all(fn._cache_size() == 1 for fn in dec._prefill_cache.values())


def test_a_prefill_fault_inside_a_scan_unwinds_and_replays(
        model, one_at_a_time):
    """The `prefill_chunk` site fires at the second reservation of the
    first scan, before any device call of the scan: that admission is
    unwound and replayed, the others of the scan go out as a pack."""
    plan = {"seed": 3, "sites": {"prefill_chunk": {"p": 1.0,
                                                   "window": [1, 2]}}}
    dec, got, spans = _served(model, fault_plan=plan,
                              pipelined_admission=True)
    assert got == one_at_a_time[1]
    assert dec.replays == 1 and dec.allocator.in_use == 0
    assert len(_metas(spans, "serve:admit")) == len(_requests())
    assert sum(m["prompts"] for m in _metas(spans, "serve:prefill")) \
        == len(_requests())
