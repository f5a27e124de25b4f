"""The trace reduction on events recorded on the chip.

``data/trace-v5e-batch.json`` is an extract of the profile of one traced run
of ``glm4-9b-s4-f32.batch`` on a TPU v5e: the device ops and program
executions of half a second inside the window, the program executions of the
harness's warm-up replay, and the harness's spans over both, in nanoseconds
from the replay's start (``what`` in the file says which run).
"""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import cell  # noqa: E402
import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data", "trace-v5e-batch.json")
CONFIG = "glm4-9b-s4-f32"


@pytest.fixture(scope="module")
def rec():
    with open(DATA) as f:
        d = json.load(f)

    def events(rows):
        return [tr.Event(n, float(s), float(e), {}) for n, s, e in rows]
    return {"ops": events(d["ops"]), "modules": events(d["modules"]),
            "spans": events(d["spans"]), "slice": tuple(d["slice"]),
            "replay": tuple(d["replay"])}


def _order(conf):
    """The engine steps the replayed warm-up dispatches, as run.py maps
    them: one decode program per decode bucket, one chunk program per
    chunk-table width, then the page copy and the scrub."""
    from repro.serve import kv_cache as kvc
    e = conf["engine"]
    chunk = max(32, e["page_size"])      # the engine's default chunk
    per_slot = e["max_seq"] // e["page_size"]
    chunk_buckets = kvc.decode_block_buckets(per_slot
                                             + chunk // e["page_size"])
    widths = {kvc.chunk_table_width(p0, chunk, e["page_size"], chunk_buckets)
              for p0 in range(0, e["max_seq"] - 1, chunk)}
    buckets = kvc.decode_block_buckets(per_slot)
    return (["decode"] * len(buckets) + ["prefill_chunk"] * len(widths)
            + ["cow_copy", "scrub_block"]), buckets


def test_busy_and_idle_fill_the_slice(rec):
    lo, hi = rec["slice"]
    busy = tr.busy_ns(rec["ops"], lo, hi)
    idle = sum(e - s for s, e in tr.idle_gaps(rec["ops"], lo, hi))
    assert 0 < busy < hi - lo
    assert busy + idle == pytest.approx(hi - lo, abs=1.0)


def test_the_replay_maps_every_engine_program(rec):
    order, _ = _order(cell.load_config(CONFIG))
    programs = tr.program_map(rec["modules"], rec["replay"], order,
                              "jit_counted")
    assert sorted(programs.values()) == sorted(order)


def test_paged_attention_runs_inside_engine_programs(rec):
    order, _ = _order(cell.load_config(CONFIG))
    programs = tr.program_map(rec["modules"], rec["replay"], order,
                              "jit_counted")
    lo, hi = rec["slice"]
    dev = {"ops": rec["ops"], "modules": rec["modules"]}
    owner = tr.op_program(rec["ops"], rec["modules"])
    steps = [programs.get(m) for o, m in zip(rec["ops"], owner)
             if lo <= o.start < hi and tr.is_kernel(o, "paged_attention")]
    assert steps and "decode" in steps
    assert set(steps) <= {"decode", "prefill_chunk"}
    total = sum(tr.kernel_seconds(dev, "paged_attention", programs, s, lo, hi)
                for s in ("decode", "prefill_chunk"))
    assert 0 < total < (hi - lo) * 1e-9


def test_decode_kernel_share_of_its_roofline_is_under_one(rec):
    """Each decode kernel call, counted as if every slot attended the widest
    context its program's bucket allows (more work than it did), stays at
    or under the chip's roofline."""
    conf = cell.load_config(CONFIG)
    order, buckets = _order(conf)
    programs = tr.program_map(rec["modules"], rec["replay"], order,
                              "jit_counted")
    decode = [m for m, s in programs.items() if s == "decode"]
    bucket = dict(zip(decode, buckets))   # replayed in bucket order
    e = conf["engine"]
    one_layer = dict(conf["model"], num_layers=1)
    peak = peaks.peaks("TPU v5 lite")
    lo, hi = rec["slice"]
    owner = tr.op_program(rec["ops"], rec["modules"])
    shares = []
    for o, m in zip(rec["ops"], owner):
        if (lo <= o.start < hi and m in bucket
                and tr.is_kernel(o, "paged_attention")):
            ops, nbytes = work.paged_decode(
                one_layer, bucket[m] * e["page_size"], e["kv_bits"],
                e["page_size"])
            shares.append(work.roofline_share(
                e["slots"] * ops, e["slots"] * nbytes,
                (o.end - o.start) * 1e-9, peak))
    assert shares
    assert max(shares) <= 1.0
