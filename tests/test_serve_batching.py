"""Continuous-batching serve-plane tests: mid-batch admit/retire/cancel,
typed overload sheds with retry, page-quota enforcement, the keyed
pending-attach table, cross-pod byref handoff accounting, and the
failed-admit leak regression."""

import threading
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.errors import ChannelError, Overloaded
from repro.models import build_model
from repro.serving import PagedKVPool, PoolConfig, ServeEngine
from repro.serving.engine import DecodeService, FN_ATTACH, Request
from repro.serving.kv_pool import PoolPages
from repro.serving.paged_model import prefill_kv


@pytest.fixture(scope="module")
def small_lm():
    cfg = replace(get_smoke_config("yi_9b"), num_layers=2)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def mk_engine(cfg, params, *, num_pages=64, page_tokens=8, maxp=8, **kw):
    pc = PoolConfig(num_pages=num_pages, page_tokens=page_tokens,
                    max_pages_per_seq=maxp)
    return ServeEngine(cfg, params, pc, backend="ref", **kw)


class TestContinuousBatching:
    def test_midbatch_admit_retire_cancel(self, small_lm):
        """Three streams admitted at different times into ONE batched
        decode loop; one retires early (shorter budget), one is
        cancelled mid-batch; every delivered token must equal the
        stream's solo (sequential) generation — continuous batching may
        change the schedule, never the tokens."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params)
        pa, pb, pc_ = [5, 6, 7, 8], [9, 10, 11], [2, 3, 4, 5, 6]
        ref_a = list(eng.generate_tokens(pa, 8))
        ref_b = list(eng.generate_tokens(pb, 5))
        ref_c = list(eng.generate_tokens(pc_, 8))
        free0 = eng.pool.heap.free_pages()
        steps0 = eng.stream_steps

        ga = eng.generate_tokens(pa, 8)
        a = [next(ga), next(ga)]          # admit A, step with B=1
        gb = eng.generate_tokens(pb, 5)
        b = [next(gb)]                    # admit B mid-batch
        gc = eng.generate_tokens(pc_, 8)
        c = [next(gc), next(gc)]          # admit C mid-batch (B=3 live)
        # interleave pulls: whoever finds its buffer dry steps ALL live
        b += [next(gb) for _ in range(4)]   # B retires (5 tokens)
        assert next(gb, None) is None
        gc.close()                          # cancel C mid-batch
        a += list(ga)                       # drain A to exhaustion

        assert a == ref_a
        assert b == ref_b
        assert c == ref_c[: len(c)]
        # batching really formed (≥2 streams in one decode step) and
        # cost fewer batched steps than the solo generations summed
        assert eng.peak_stream_batch >= 3
        assert eng.stream_steps - steps0 < (8 - 1) + (5 - 1) + (8 - 1)
        # cancel + retire returned every page and seal
        assert eng.scheduler.slots == []
        assert eng.pool.heap.free_pages() == free0
        assert eng.pool.stats()["sealed_pages"] == 0
        # TTFT: the first token of every stream came from its prefill,
        # never waited on the batch (≤ 2 decode steps by the gate)
        assert all(t <= 2 for t in eng.ttft_steps)

    def test_cancel_frees_pages_and_seals_exactly_once(self, small_lm):
        """A client disconnect/cancel mid-stream aborts the server
        generator; its pages and seals are returned exactly once."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params)
        free0 = eng.pool.heap.free_pages()
        frees = []
        orig_free = eng.pool.free_seq
        eng.pool.free_seq = (
            lambda pages: (frees.append(tuple(pages)), orig_free(pages))[1])
        try:
            st = eng.stub.generate_stream.stream([5, 6, 7, 8], 40,
                                                 inline=True)
            it = iter(st)
            got = [next(it) for _ in range(3)]
            assert len(got) == 3
            st.close()                   # cancel sentinel in consumed word
            eng.channel.pump_streams()   # server observes it → abort
            assert len(frees) == 1       # exactly once, not zero, not two
            assert eng.scheduler.slots == []
            assert eng.pool.heap.free_pages() == free0
            assert eng.pool.stats()["sealed_pages"] == 0
        finally:
            eng.pool.free_seq = orig_free

    def test_pool_exhaustion_sheds_stream_with_retry_after(self, small_lm):
        """When pages run out, stream admission sheds a *typed*
        Overloaded (retry-after µs on the wire, PR6 contract) instead of
        wedging — and the retry succeeds once pages free up."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params, num_pages=16, maxp=16)
        f0 = eng.pool.heap.free_pages()
        assert f0 >= 5
        # stream A pins all but 2 free pages for its whole generation
        hog_new = (f0 - 2) * eng.pool.pc.page_tokens - 3
        ga = eng.generate_tokens([1, 2, 3], hog_new)
        next(ga)
        assert eng.pool.heap.free_pages() == 2
        # stream B needs 3 pages → typed shed through the chunk chain
        with pytest.raises(Overloaded) as ei:
            list(eng.stub.generate_stream.stream([4, 5, 6, 7], 17,
                                                 inline=True))
        assert ei.value.retry_after_s > 0
        assert eng.shed_admits >= 1
        ga.close()                       # A's pages return to the pool
        retry = list(eng.stub.generate_stream.stream([4, 5, 6, 7], 17,
                                                     inline=True))
        assert len(retry) == 17
        assert eng.pool.heap.free_pages() == f0

    def test_page_quota_sheds_over_quota_admit(self, small_lm):
        """The once-dead ``quota_pages`` knob now drives the §5.4
        orchestrator page quota: an admit that would exceed it sheds
        with Overloaded; in-quota admits are untouched."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params, quota_pages=4)
        with pytest.raises(Overloaded):
            next(eng.generate_tokens([1, 2, 3, 4], 60))  # 8 pages > 4
        assert eng.shed_admits >= 1
        assert eng.orch.page_quota(eng.conn_id) == 4
        toks = list(eng.generate_tokens([1, 2], 6))      # 1 page ≤ 4
        assert len(toks) == 6
        assert eng.pool.stats()["sealed_pages"] == 0

    def test_threaded_concurrent_streams_match_sequential(self, small_lm):
        """3 real client threads (own connections) through one threaded
        decode worker: every stream's tokens equal its solo run, and
        nothing leaks — the RPC-plane version of the batching test."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params, serve_threaded=True, max_active=4)
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(3)]
            refs = [list(eng.generate_tokens(p, 24)) for p in prompts]
            free0 = eng.pool.heap.free_pages()
            outs = [None] * 3
            errors = []
            barrier = threading.Barrier(3)

            def client(i):
                try:
                    stub = eng.router.stub(eng.endpoint_name, DecodeService,
                                           pid=30 + i, pod="pod0")
                    barrier.wait()
                    outs[i] = list(stub.generate_stream.stream(
                        prompts[i], 24, timeout=60.0))
                except BaseException as e:   # noqa: BLE001
                    errors.append((i, e))
                    barrier.abort()

            threads = [threading.Thread(target=client, args=(i,),
                                        daemon=True) for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
                assert not t.is_alive(), "client thread wedged"
            assert not errors, f"client failures: {errors!r}"
            assert outs == refs              # zero lost/mismatched tokens
            assert eng.scheduler.slots == []
            assert eng.pool.heap.free_pages() == free0
            assert eng.pool.stats()["sealed_pages"] == 0
        finally:
            eng.shutdown()


class TestEmptyPaddingRows:
    def test_padding_rows_are_empty_and_served_tokens_agree(
            self, small_lm, monkeypatch):
        """Three live streams in a batch of 8: rows 3..7 are padding of
        length 0 (row 0's block table and position, nothing attended,
        nothing written). The kernel in interpret mode serves exactly
        the oracle's tokens, and each ``serve.step`` span counts the
        pages the live rows hold."""
        from repro.serving import engine as engine_mod

        cfg, m, params = small_lm
        T = 8
        prompts = [[5, 6, 7, 8], [9, 10, 11], [2, 3, 4, 5, 6, 7, 8, 9, 10]]
        served = {}
        for backend in ("ref", "interpret"):
            eng = ServeEngine(cfg, params,
                              PoolConfig(num_pages=64, page_tokens=T,
                                         max_pages_per_seq=8),
                              max_active=8, backend=backend)
            sch = eng.scheduler
            stats, want = [], []
            real_span = engine_mod.span

            def recording(name, **kw):
                if name == "serve.step":
                    stats.append(kw)
                    want.append(sum(
                        -(-(s.pos + 1) // T) for s in sch.slots
                        if not s.released and s.produced < s.max_new))
                return real_span(name, **kw)

            monkeypatch.setattr(engine_mod, "span", recording)
            gens = [eng.generate_tokens(p, 12) for p in prompts]
            outs = [[next(g)] for g in gens]     # admitted: 3 live rows
            live = list(sch.slots)
            _, pos, bt, lens = (np.asarray(a)
                                for a in sch.batch_inputs(live)[:4])
            assert lens.shape == (8,)
            assert lens[:3].tolist() == [s.pos + 1 for s in live]
            assert (lens[3:] == 0).all()
            assert (bt[3:] == bt[0]).all() and (pos[3:] == pos[0]).all()
            for _ in range(11):
                for g, out in zip(gens, outs):
                    out.append(next(g))
            assert all(next(g, None) is None for g in gens)
            monkeypatch.setattr(engine_mod, "span", real_span)
            assert [s["batch"] for s in stats] == [3] * 11
            assert [s["live_pages"] for s in stats] == want
            assert want[0] == 1 + 1 + 2 and want[-1] == 2 + 2 + 3
            assert eng.oob_events == 0
            served[backend] = outs
        assert served["interpret"] == served["ref"]
        # a lone stream (seven empty rows) serves the batched tokens too
        assert [list(eng.generate_tokens(p, 12)) for p in prompts] \
            == served["interpret"]


class TestAttachTable:
    def test_concurrent_pending_attaches_keyed_by_rid(self, small_lm):
        """Two handoffs in flight at once: the pending table is keyed by
        rid, so attaches landing out of order adopt the right request
        (the old single-slot field adopted whichever came last)."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params, max_active=4)
        reqs = []
        for prompt in ([1, 2, 3], [4, 5, 6]):
            req = Request(eng._mint_rid(), list(prompt), 4)
            req.pages = eng.pool.alloc_seq(len(prompt) + 4, eng.conn_id)
            req.out = [1]
            req.pos = len(prompt)
            eng._pending_attach[req.rid] = req
            reqs.append(req)
        for req in reversed(reqs):       # land out of order
            eng._handoff(req)
        assert [r.rid for r in eng.active] == [reqs[1].rid, reqs[0].rid]
        assert eng._pending_attach == {}
        eng.run_until_drained()
        assert all(eng.result(r.rid) is not None for r in reqs)

    def test_attach_unknown_rid_raises_typed(self, small_lm):
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params)
        with pytest.raises(ChannelError):
            eng.stub.attach(999, 4, [1, 2], timeout=5.0, inline=True)
        assert eng.active == []

    def test_attach_mismatch_raises_typed_not_assert(self, small_lm):
        """A forged handoff (pages disagree with the prefill record)
        raises ChannelError — a bare assert would vanish under -O and
        adopt the wrong pages."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params)
        req = Request(eng._mint_rid(), [1, 2, 3], 4)
        req.pages = eng.pool.alloc_seq(7, eng.conn_id)
        eng._pending_attach[req.rid] = req
        forged = [(p + 1) % eng.pool.pc.num_pages for p in req.pages]
        with pytest.raises(ChannelError):
            eng.stub.attach(req.rid, 3, forged, timeout=5.0, inline=True)
        assert eng.active == []
        eng.pool.free_seq(req.pages)


def _alloc_stats(pool):
    """heap.stats() minus monotonic counters (perm_epoch advances on
    every seal/release — leak-irrelevant)."""
    st = dict(pool.heap.stats())
    st.pop("perm_epoch", None)
    return st


class TestFailedAdmitLeak:
    def test_prefill_fault_returns_pages(self, small_lm):
        """A fault between page allocation and handoff must leave the
        heap exactly at its baseline (the alloc_seq partial-allocation
        audit, engine-level) and the request retryable."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params)
        base = _alloc_stats(eng.pool)
        calls = {"n": 0}
        orig = eng.pool.write_prefill

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected prefill fault")
            return orig(*a, **kw)

        eng.pool.write_prefill = flaky
        try:
            rid = eng.submit([1, 2, 3, 4], max_new=4)
            # an untyped fault is rolled back, then surfaces to the caller
            with pytest.raises(RuntimeError, match="injected prefill"):
                eng._admit()
            assert eng.queue                       # requeued, not lost
            assert eng._pending_attach == {}
            assert _alloc_stats(eng.pool) == base
            eng.run_until_drained()                # retry succeeds
            assert len(eng.result(rid)) == 4
        finally:
            eng.pool.write_prefill = orig

    def test_handoff_fault_releases_seals_and_pages(self, small_lm):
        """A fault in the attach RPC itself (after the flight seals are
        taken) must release the seals AND the pages — the leak the
        heap-stats regression gate exists to catch."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params)
        base = _alloc_stats(eng.pool)
        orig_fn = eng.channel.functions[FN_ATTACH]

        def boom(ctx, arg):
            raise RuntimeError("injected attach fault")

        eng.channel.functions[FN_ATTACH] = boom
        try:
            rid = eng.submit([1, 2, 3, 4], max_new=4)
            assert eng._admit() == 0
            assert eng._pending_attach == {}
            assert _alloc_stats(eng.pool) == base
        finally:
            eng.channel.functions[FN_ATTACH] = orig_fn
        eng.run_until_drained()
        assert len(eng.result(rid)) == 4


class TestByrefHandoff:
    def test_same_pod_byref_is_pointer_passing(self, small_lm):
        """Over the CXL route a byref page set resolves to the raw
        pointers — zero KV bytes move, and the decode worker adopts the
        request against the very same pages."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params)
        prompt = [5, 6, 7, 8]
        ref = list(eng.generate_tokens(prompt, 6))
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, k, v = prefill_kv(eng.model, params, toks)
        pages = eng.pool.alloc_seq(len(prompt) + 6, eng.conn_id)
        eng.pool.write_prefill(k[:, 0], v[:, 0], pages, len(prompt))
        first = int(jnp.argmax(logits[0]))
        pp = PoolPages(eng.pool, pages, backend="ref")
        rid = 7001
        eng.stub.attach_remote(rid, prompt, first, 6, pp,
                               timeout=10.0, inline=True)
        assert pp.last_moved_bytes == 0          # pointer route
        assert eng.pool.byref_bytes_in == 0
        assert [r.rid for r in eng.active] == [rid]
        assert eng.active[0].pages == pages      # the SAME pages
        eng.run_until_drained()
        assert eng.result(rid) == ref

    def test_cross_pod_byref_migrates_and_accounts_bytes(self, small_lm):
        """Prefill in one pod, decode in another, same stub surface: the
        byref argument bulk-migrates the KV through scope_copy exactly
        once, byte accounting matches pages × page_bytes on both pools,
        and the decoded tokens equal the same-pod generation."""
        cfg, m, params = small_lm
        eng = mk_engine(cfg, params, pod="dpod")
        prompt = [5, 6, 7, 8]
        ref = list(eng.generate_tokens(prompt, 6))

        pc = eng.pool.pc
        src_pool = PagedKVPool(eng.orch, cfg, pc, owner_pid=21, pod="ppod")
        stub = eng.router.stub(eng.endpoint_name, DecodeService,
                               pid=21, pod="ppod")
        assert stub.connection.transport == "fallback"

        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, k, v = prefill_kv(eng.model, params, toks)
        pages = src_pool.alloc_seq(len(prompt) + 6, 21)
        src_pool.write_prefill(k[:, 0], v[:, 0], pages, len(prompt))
        first = int(jnp.argmax(logits[0]))

        dst_free0 = eng.pool.heap.free_pages()
        pp = PoolPages(src_pool, pages, backend="ref")
        rid = 7002
        stub.attach_remote(rid, prompt, first, 6, pp, timeout=10.0)

        expected = len(pages) * src_pool.page_bytes
        assert pp.last_moved_bytes == expected
        assert src_pool.byref_bytes_out == expected
        assert eng.pool.byref_bytes_in == expected
        # destination pages were minted in the decode pod's pool
        assert eng.pool.heap.free_pages() == dst_free0 - len(pages)
        eng.run_until_drained()
        assert eng.result(rid) == ref            # migrated KV decodes same
        assert eng.pool.heap.free_pages() == dst_free0
        src_pool.free_seq(pages)
