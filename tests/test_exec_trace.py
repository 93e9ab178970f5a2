"""The span-and-counter recorder (``repro.exec.trace``) and the spans the
serving engine records with it."""
import math

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.exec import trace
from repro.serve import Request, ServingEngine
from repro.serve.engine import prompt_lengths


class FakeClock:
    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


# --------------------------------------------------------------------------- #
# Recorder
# --------------------------------------------------------------------------- #
def test_spans_carry_parent_and_request_ids():
    rec = trace.Recorder(clock=FakeClock())
    with rec.span("decode") as outer:
        with rec.span("page_out", rid="a"):
            pass
        with rec.span("page_out", rid="b"):
            pass
    with rec.span("retire"):
        pass
    by_name = {}
    for s in rec.spans():
        by_name.setdefault(s.name, []).append(s)
    (dec,), (ret,) = by_name["decode"], by_name["retire"]
    assert dec.id == outer.id and dec.parent is None and ret.parent is None
    assert [s.parent for s in by_name["page_out"]] == [dec.id, dec.id]
    assert [s.rid for s in by_name["page_out"]] == ["a", "b"]
    # children close first; every child lies inside its parent
    assert [s.name for s in rec.spans()] == ["page_out", "page_out",
                                             "decode", "retire"]
    for s in by_name["page_out"]:
        assert dec.start < s.start < s.end < dec.end
    assert len({s.id for s in rec.spans()}) == 4


def test_counters_go_to_the_total_and_the_innermost_span():
    rec = trace.Recorder(clock=FakeClock())
    with rec.span("decode"):
        rec.count("decode_steps")
        with rec.span("page_out", rid="a"):
            rec.count("page_out_bytes", 100)
            rec.count("page_out_bytes", 28)
    rec.count("admitted", 2)                     # outside every span
    assert rec.counters == {"decode_steps": 1, "page_out_bytes": 128,
                            "admitted": 2}
    po, dec = rec.spans()
    assert po.counts == {"page_out_bytes": 128}
    assert dec.counts == {"decode_steps": 1}
    with rec.span("retire"):
        pass
    assert rec.spans()[-1].counts is None        # a span that counted none


def test_the_ring_evicts_the_oldest_and_counts_them(monkeypatch):
    monkeypatch.setattr(trace, "CAPACITY", 4)
    rec = trace.Recorder(clock=FakeClock())
    for i in range(6):
        with rec.span("page_out", rid=f"r{i}"):
            rec.count("page_out_rows")
    assert rec.closed == 6 and rec.dropped == 2
    assert [s.rid for s in rec.spans()] == ["r2", "r3", "r4", "r5"]
    assert rec.counters == {"page_out_rows": 6}   # counters are not evicted
    with rec.span("retire"):
        pass
    assert rec.closed == 7 and rec.dropped == 3
    assert [s.name for s in rec.spans()][-2:] == ["page_out", "retire"]


def test_a_span_closes_on_an_exception():
    rec = trace.Recorder(clock=FakeClock())
    with pytest.raises(KeyError):
        with rec.span("decode"):
            with rec.span("decode.step"):
                raise KeyError("x")
    assert [s.name for s in rec.spans()] == ["decode.step", "decode"]
    with rec.span("retire"):
        pass
    assert rec.spans()[-1].parent is None


# --------------------------------------------------------------------------- #
# The engine's spans and counters
# --------------------------------------------------------------------------- #
ARCH = ARCHS["qwen2-1.5b"].reduced()
MAX_SEQ = 24


def _requests():
    rng = np.random.default_rng(3)
    lens = [(5, 4), (9, 6), (3, 1), (7, 5), (6, 3)]
    return [Request(rid=f"q{i}", prompt_len=p, max_new=n,
                    prompt=tuple(int(t) for t in rng.integers(3, ARCH.vocab, p)))
            for i, (p, n) in enumerate(lens)]


@pytest.fixture(scope="module")
def served():
    eng = ServingEngine(ARCH, slots=2, max_seq=MAX_SEQ, block_size=4,
                        prefill_chunk=4)
    report = eng.run(_requests())
    row = eng._extract_row(eng.working, 0)
    row_bytes = sum(leaf.nbytes for leaf in (row["k"], row["v"]))
    return eng, report, eng.recorder.spans(), row_bytes


def _column_bytes(eng):
    """Bytes of one slot's column: one position of each paged leaf."""
    return sum(math.prod(m.row_shape) // eng.max_seq * m.dtype.itemsize
               for m in eng.kv.leaves if m.paged)


def test_every_decode_iteration_pages_out_each_active_row(served):
    eng, report, spans, _ = served
    c = eng.recorder.counters
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    decodes = [s for s in spans if s.name == "decode"]
    assert len(decodes) == report.decode_steps == c["decode_steps"]
    active = 0
    for d in decodes:
        names = [c.name for c in kids[d.id]]
        step, = [c for c in kids[d.id] if c.name == "decode.step"]
        n = step.counts["active_slot_steps"]
        active += n
        assert 1 <= n <= eng.slots
        # one column transfer for all slots, then a pool write per row
        po, = [c for c in kids[d.id] if c.name == "page_out"]
        assert po.rid is None and po.counts["page_out_rows"] == n
        assert po.counts["page_out_bytes"] == eng.slots * _column_bytes(eng)
        assert names.count("pool_write") == n
        assert names.count("retire") == 1 and d.parent is None
        assert len({c.rid for c in kids[d.id] if c.name == "pool_write"}) == n
    assert active == c["active_slot_steps"]


#: Each prompt length of ``_requests`` and the length its prefill row is
#: cut to on the way to the host (``prompt_lengths(4, 24)``).
CUT = {5: 8, 9: 16, 3: 4, 7: 8, 6: 8}


def test_page_out_bytes_are_rows_times_the_row(served):
    eng, report, _, row_bytes = served
    c = eng.recorder.counters
    rows = c["active_slot_steps"] + c["admitted"]    # decode + prefill rows
    assert c["page_out_rows"] == rows
    # decode: every slot's column each step; prefill: the prompt's
    # positions, cut to the next of the prompt lengths
    assert eng._prompt_lengths == prompt_lengths(4, MAX_SEQ) == (4, 8, 16, 24)
    assert c["page_out_bytes"] == \
        c["decode_steps"] * eng.slots * _column_bytes(eng) + \
        sum(CUT[r.prompt_len] for r in _requests()) * _column_bytes(eng)
    assert _column_bytes(eng) * eng.max_seq == row_bytes
    assert c["admitted"] == len(_requests())
    assert c["prefill_chunks"] == report.prefill_chunks == \
        sum(-(-r.prompt_len // 4) for r in _requests())


def test_an_admission_pages_out_its_prompt_cut_to_a_length(served):
    """One prefill's ``page_out``: the bucketed prompt length times one
    column's bytes, whatever the rest of the row holds."""
    eng, _, spans, _ = served
    plen = {r.rid: r.prompt_len for r in _requests()}
    by_id = {s.id: s for s in spans}
    outs = [s for s in spans if s.name == "page_out"
            and by_id[s.parent].name == "prefill"]
    assert sorted(s.rid for s in outs) == sorted(plen)
    for s in outs:
        assert s.counts == {"page_out_rows": 1, "page_out_bytes":
                            CUT[plen[s.rid]] * _column_bytes(eng)}


def test_psum_bytes_count_nothing_at_tp1(served):
    """On one device the step programs have no collective: the counter is
    charged with every decode step and prefill chunk, at 0."""
    eng, _, spans, _ = served
    assert eng._psum_bytes == {"decode": 0, "prefill": 0}
    assert eng.recorder.counters["psum_bytes"] == 0
    for name in ("decode.step", "prefill.chunks"):
        charged = [s for s in spans if s.name == name]
        assert charged and all(s.counts["psum_bytes"] == 0 for s in charged)


def test_prefill_spans_nest_under_admit(served):
    _, report, spans, _ = served
    by_id = {s.id: s for s in spans}
    prefills = [s for s in spans if s.name == "prefill"]
    assert sorted(s.rid for s in prefills) == sorted(
        r.rid for r in _requests())
    for p in prefills:
        assert by_id[p.parent].name == "admit"
        kids = [s for s in spans if s.parent == p.id]
        assert [k.name for k in kids] == ["prefill.chunks", "page_out",
                                          "pool_write", "seat"]
        assert all(k.rid == p.rid for k in kids)


def test_every_iteration_has_one_admit_span_that_counts_its_admissions(
        served):
    _, report, spans, _ = served
    admits = [s for s in spans if s.name == "admit"]
    assert len(admits) == report.iterations
    assert all(a.parent is None for a in admits)
    per_admit = {}
    for s in spans:
        if s.name == "prefill":
            per_admit[s.parent] = per_admit.get(s.parent, 0) + 1
    assert {a.id: a.counts["admitted"] for a in admits if a.counts} == \
        per_admit


def test_report_counts_are_the_counters_of_its_own_run():
    eng = ServingEngine(ARCH, slots=2, max_seq=MAX_SEQ, block_size=4,
                        prefill_chunk=4)
    first = eng.run(_requests()[:2])
    before = dict(eng.recorder.counters)
    second = eng.run(_requests()[2:])
    c = eng.recorder.counters
    assert first.decode_steps == before["decode_steps"] > 0
    assert second.decode_steps == c["decode_steps"] - before["decode_steps"]
    assert second.prefill_chunks == \
        c["prefill_chunks"] - before["prefill_chunks"] == \
        sum(-(-r.prompt_len // 4) for r in _requests()[2:])
