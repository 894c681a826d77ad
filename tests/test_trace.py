"""The program's tracing primitive (dlrover_tpu/common/trace.py) and
the spans, events and counts the serving path leaves in its ring:
what a record may hold, how spans nest, and that every total the
engine and the scheduler already kept is fed from the same clock
readings as the span that covers the same boundary."""

import dataclasses
import gc
import json
import subprocess
import sys
import threading
import time
import urllib.request
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace
from dlrover_tpu.common.trace import COUNTS, DUR, ID, NAME, PARENT, REQ, WALL
from dlrover_tpu.models import llama
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.gateway import ServingGateway
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.scheduler import RequestScheduler, SloConfig
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    Checkpointer,
    StorageType,
)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def empty_ring():
    trace.clear()
    yield
    trace.clear()


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("chunk", 4)
    kw.setdefault("pad_id", -1)
    return ContinuousBatcher(cfg, params, **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).tolist() for n in lengths]


def _named(name, records=None):
    records = trace.snapshot() if records is None else records
    return [r for r in records if r[NAME] == name]


class TestRing:
    def test_bounded(self):
        for i in range(trace.RING_SIZE + 10):
            trace.event("e", i)
        records = trace.snapshot()
        assert len(records) == trace.RING_SIZE
        assert records[0][REQ] == 10  # the oldest ten fell out

    @pytest.mark.parametrize(
        "value", [np.zeros(3), np.int64(3), [1, 2], object()],
        ids=["array", "numpy_scalar", "list", "object"],
    )
    def test_a_record_holds_numbers_and_strings_only(self, value):
        with pytest.raises(TypeError):
            trace.span("s", bad=value)
        with pytest.raises(TypeError):
            trace.event("e", 1, bad=value)
        with trace.span("s") as sp:
            with pytest.raises(TypeError):
                sp.set(bad=value)
        assert _named("s")[0][COUNTS] == {}

    def test_snapshot_cuts_a_window_and_clear_empties(self):
        trace.event("early", 1)
        t0 = time.time()
        time.sleep(0.002)
        with trace.span("inside"):
            pass
        time.sleep(0.002)
        t1 = time.time()
        time.sleep(0.002)
        trace.event("late", 2)
        assert [r[NAME] for r in trace.snapshot(t0, t1)] == ["inside"]
        assert len(trace.snapshot()) == 3
        trace.clear()
        assert trace.snapshot() == []

    def test_event_is_a_plain_tuple_without_extent(self):
        with trace.span("outer") as sp:
            trace.event("request", 7, t_first=1.5, t_end=None, tokens=3)
        (ev,) = _named("request")
        assert type(ev) is tuple and len(ev) == 7
        assert ev[DUR] == 0.0 and ev[REQ] == 7 and ev[PARENT] == sp.id
        assert ev[COUNTS] == {"t_first": 1.5, "t_end": None, "tokens": 3}


class TestNesting:
    def test_spans_name_their_parent(self):
        with trace.span("a") as a:
            with trace.span("b") as b:
                with trace.span("c") as c:
                    pass
            with trace.span("d") as d:
                pass
        by_name = {r[NAME]: r for r in trace.snapshot()}
        assert by_name["a"][PARENT] == 0
        assert by_name["b"][PARENT] == a.id == by_name["a"][ID]
        assert by_name["c"][PARENT] == b.id
        assert by_name["d"][PARENT] == a.id
        assert len({a.id, b.id, c.id, d.id}) == 4
        # a parent contains its children on both clocks
        assert by_name["a"][DUR] >= by_name["b"][DUR] + by_name["d"][DUR]
        assert by_name["a"][WALL] <= by_name["b"][WALL]

    def test_a_raising_block_still_records_and_unwinds(self):
        with pytest.raises(ValueError):
            with trace.span("outer"):
                with trace.span("inner"):
                    raise ValueError("boom")
        assert [r[NAME] for r in trace.snapshot()] == ["inner", "outer"]
        with trace.span("after"):
            pass
        assert _named("after")[0][PARENT] == 0

    def test_nesting_is_per_thread(self):
        inside = threading.Event()
        release = threading.Event()

        def other():
            with trace.span("other.outer"):
                inside.set()
                assert release.wait(10)
                with trace.span("other.inner"):
                    pass

        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(10)
        with trace.span("main.outer") as main_outer:
            with trace.span("main.inner"):
                pass
        release.set()
        t.join(10)
        assert not t.is_alive()
        by_name = {r[NAME]: r for r in trace.snapshot()}
        assert by_name["main.outer"][PARENT] == 0
        assert by_name["main.inner"][PARENT] == main_outer.id
        assert by_name["other.inner"][PARENT] == by_name["other.outer"][ID]

    def test_16_threads_lose_nothing(self):
        n_threads, per_thread = 16, 400
        start = threading.Barrier(n_threads)

        def work(k):
            start.wait(10)
            for i in range(per_thread):
                with trace.span("w", req=k, i=i):
                    pass

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [
                threading.Thread(target=work, args=(k,))
                for k in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(old)
        records = _named("w")
        assert len(records) == n_threads * per_thread
        assert len({r[ID] for r in records}) == len(records)
        for k in range(n_threads):
            mine = [r[COUNTS]["i"] for r in records if r[REQ] == k]
            assert mine == list(range(per_thread))


class TestWithoutJax:
    def test_a_process_without_jax_traces_and_stays_without(self):
        code = (
            "import sys\n"
            "from dlrover_tpu.common import trace\n"
            "with trace.span('agent.detect', pid=3) as sp:\n"
            "    trace.event('e', 1)\n"
            "assert len(trace.snapshot()) == 2\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "assert 'numpy' not in sys.modules, 'numpy was imported'\n"
            "print('ok')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"

    def test_the_annotation_carries_the_prefixed_name(self, monkeypatch):
        opened = []

        class Recorder:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                opened.append(("enter", self.name))

            def __exit__(self, *exc):
                opened.append(("exit", self.name))

        monkeypatch.setattr(trace, "_annotate", Recorder)
        with trace.span("engine.step"):
            pass
        assert opened == [
            ("enter", "dlrover:engine.step"), ("exit", "dlrover:engine.step"),
        ]


class TestEngineSpans:
    def test_step_is_wait_plus_host_and_feeds_the_totals(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        for p in _prompts((5, 12, 3, 9), seed=1):
            eng.submit(p)
        n = 0
        while eng.has_work():
            eng.step()
            n += 1
            assert eng.last_step_s == _named("engine.step")[-1][DUR]
        steps = _named("engine.step")
        assert len(steps) == n
        host_ms = sum(
            (r[DUR] - r[COUNTS]["wait_s"]) * 1e3 for r in steps
        )
        stats = eng.step_stats()
        assert stats["host_ms"] == pytest.approx(host_ms, rel=1e-9)
        harvests = _named("engine.harvest")
        assert stats["device_wait_ms"] == pytest.approx(
            sum(r[COUNTS]["wait_s"] for r in harvests) * 1e3, rel=1e-9
        )
        assert stats["dispatches"] == len(harvests)
        # every wait of a step is a harvest inside it
        for step in steps:
            inside = [r for r in harvests if r[PARENT] == step[ID]]
            assert step[COUNTS]["wait_s"] == pytest.approx(
                sum(r[COUNTS]["wait_s"] for r in inside), rel=1e-9
            )
            assert 0.0 <= step[COUNTS]["wait_s"] <= step[DUR]
        assert steps[0][COUNTS]["alive"] == 2
        assert steps[-1][COUNTS]["alive"] == 0
        assert all(type(r[COUNTS]["live_tokens"]) is int for r in steps)

    @pytest.mark.parametrize("depth", [0, 1], ids=["sync", "in-flight"])
    def test_step_carries_the_device_span_it_hid(self, model, depth):
        """`overlap_s`: of the harvested dispatch's device span (its
        enqueue to the end of its fetch), what the host did not spend
        waiting. A caller that takes 20 ms between two steps has
        hidden at least that, where a dispatch was left in flight."""
        cfg, params = model
        eng = _engine(cfg, params, async_depth=depth)
        for p in _prompts((5, 12, 3), seed=4):
            eng.submit(p)
        while eng.has_work():
            eng.step()
            time.sleep(0.02)
        steps = _named("engine.step")
        harvests = _named("engine.harvest")
        assert all(r[COUNTS]["overlap_s"] >= 0.0 for r in steps)
        harvested = [
            s for s in steps
            if any(h[PARENT] == s[ID] for h in harvests)
        ]
        assert len(harvested) == len(harvests) >= 3
        if depth:
            assert all(s[COUNTS]["overlap_s"] >= 0.02 for s in harvested)
        # the per-step counts sum to what /metrics' ratio is made of
        stats = eng.step_stats()
        hidden = sum(s[COUNTS]["overlap_s"] for s in steps)
        waited = sum(s[COUNTS]["wait_s"] for s in steps)
        assert stats["overlap_ratio"] == pytest.approx(
            hidden / (hidden + waited), rel=1e-6
        )
        if depth:
            assert stats["overlap_ratio"] > 0.0

    def test_admit_and_dispatch_carry_their_counts(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        prompts = _prompts((5, 20), seed=2)
        for p in prompts:
            eng.submit(p)
        while eng.has_work():
            eng.step()
        admits = _named("engine.admit")
        assert [r[COUNTS]["prompt_tokens"] for r in admits] == [5, 20]
        assert [r[COUNTS]["bucket"] for r in admits] == [16, 32]
        # no dropless experts in this model: no rows of theirs
        assert all("moe_rows" not in r[COUNTS] for r in admits)
        assert eng.prefill_stats()["admission_stall_ms"] == pytest.approx(
            sum(r[DUR] for r in admits) * 1e3, rel=1e-9
        )
        first = _named("engine.step")[0]
        assert first[COUNTS]["admit_s"] == pytest.approx(
            sum(r[DUR] for r in admits if r[PARENT] == first[ID]), rel=1e-9
        )
        dispatches = _named("engine.dispatch")
        assert dispatches and all(
            r[COUNTS]["chunk"] in (1, 2, 4) for r in dispatches
        )
        steps = {r[ID] for r in _named("engine.step")}
        assert all(r[PARENT] in steps for r in admits + dispatches)

    def test_a_block_diffusion_step_carries_its_counts(self):
        """Where the model generates by diffusion over blocks the
        `engine.step` span carries, from the harvested dispatch: the
        live slot-forwards, those of them that carried a finished
        block for its keys and values (`diff_fused`), the blocks
        finished, the ids handed to streams and the K/V cells the
        forwards read (block end x layers a live forward), beside the
        experts' counts with the meanings they have (`moe_steps` the
        forwards; a forward routes two blocks' positions a slot)."""
        import os

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import _sdar_tiny as tiny

        model = tiny.model_dict()
        cfg, params = tiny.config(model), tiny.params(model)
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=16,
            chunk=4, pad_id=-1, kv_layout="paged", page_size=8,
            denoising_steps=2, async_depth=0,
        )
        eng.submit(_prompts((8,), seed=4)[0][:8], max_new=12)
        while eng.has_work():
            eng.step()
        steps = [r[COUNTS] for r in _named("engine.step")]
        # three blocks of two forwards: dispatches of 4 and 2
        assert [s["diff_forwards"] for s in steps] == [4, 2]
        assert [s["diff_fused"] for s in steps] == [1, 1]
        assert [s["diff_commits"] for s in steps] == [2, 1]
        assert [s["diff_tokens"] for s in steps] == [8, 4]
        # 2 layers; the blocks 8..11, 12..15 and 16..19 two forwards
        assert [s["diff_cells"] for s in steps] == [
            2 * (12 * 2 + 16 * 2), 2 * (20 * 2)]
        assert [s["moe_steps"] for s in steps] == [4, 2]
        # every slot's 2 x 4 positions route top-2 in 2 layers a forward
        assert [s["moe_pairs"] for s in steps] == [
            4 * 2 * 8 * 2 * 2, 2 * 2 * 8 * 2 * 2]
        assert steps[0]["live_tokens"] == 16 and steps[0]["alive"] == 1
        dispatch = [r[COUNTS]["chunk"] for r in _named("engine.dispatch")]
        assert dispatch == [4, 2]
        assert all("diff_forwards" not in r[COUNTS]
                   for r in _named("engine.admit"))

    def test_other_models_steps_carry_no_block_counts(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        eng.submit(_prompts((5,), seed=5)[0])
        while eng.has_work():
            eng.step()
        assert all(
            not any(k.startswith("diff_") for k in r[COUNTS])
            for r in _named("engine.step")
        )

    def test_a_full_ring_keeps_no_engine_alive(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        sched = RequestScheduler(eng, slo=SloConfig(max_new_tokens=8))
        for p in _prompts((5, 7), seed=3):
            sched.submit(p)
        sched.run_to_completion()
        assert _named("engine.step") and _named("request")
        while len(trace.snapshot()) < trace.RING_SIZE:
            with trace.span("filler"):
                pass
        ref_engine, ref_sched = weakref.ref(eng), weakref.ref(sched)
        del eng, sched
        gc.collect()
        assert ref_engine() is None and ref_sched() is None
        assert len(trace.snapshot()) == trace.RING_SIZE


class TestSchedulerSpans:
    def test_four_legs_sum_to_the_time_to_first_token(self, model):
        cfg, params = model
        ticks = iter(range(10**6))
        # a clock that moves by an uneven step at every reading
        clock = lambda: next(ticks) * 0.37 + 100.0  # noqa: E731
        sched = RequestScheduler(
            _engine(cfg, params), slo=SloConfig(
                max_new_tokens=8, default_deadline_s=1e9),
            clock=clock,
        )
        reqs = [sched.submit(p) for p in _prompts((5, 12, 3, 9, 6), seed=4)]
        sched.run_to_completion()
        firsts = {
            r[REQ]: r[COUNTS] for r in _named("request")
            if r[COUNTS]["t_end"] is None
        }
        ends = {
            r[REQ]: r[COUNTS] for r in _named("request")
            if r[COUNTS]["t_end"] is not None
        }
        assert set(firsts) == set(ends) == {r.id for r in reqs}
        for req in reqs:
            c = firsts[req.id]
            legs = [
                c["t_locked"] - c["t_submit"],
                c["t_queued"] - c["t_locked"],
                c["t_admitted"] - c["t_queued"],
                c["t_first"] - c["t_admitted"],
            ]
            assert all(leg >= 0 for leg in legs), legs
            assert c["t_submit"] == req.submit_ts
            assert c["t_first"] == req.first_token_ts
            assert (c["t_locked"], c["t_queued"], c["t_admitted"]) == (
                req.locked_ts, req.queued_ts, req.admitted_ts)
            # the same four numbers telescope: exact, not approximate
            assert (
                ((legs[3] + c["t_admitted"]) - c["t_submit"])
                == req.first_token_ts - req.submit_ts
            )
            assert sum(legs) == pytest.approx(
                req.first_token_ts - req.submit_ts, abs=1e-9)
            assert ends[req.id]["tokens"] == len(req.tokens) == 8
            assert ends[req.id]["t_end"] == req.finish_ts
            assert c["submit_wall"] == req.submit_wall

    def test_submit_reads_its_wait_for_the_lock(self, model):
        cfg, params = model
        entered = threading.Event()
        main = threading.current_thread()

        def clock():
            # submit's first reading is its entry stamp
            if threading.current_thread() is not main:
                entered.set()
            return time.monotonic()

        sched = RequestScheduler(
            _engine(cfg, params), slo=SloConfig(max_new_tokens=8),
            clock=clock,
        )
        free = sched.submit(_prompts((5,), seed=5)[0])
        got = []
        t = threading.Thread(
            target=lambda: got.append(
                sched.submit(_prompts((6,), seed=6)[0]))
        )
        with sched._cond:  # held while the other thread stands in submit
            t.start()
            assert entered.wait(10)
            time.sleep(0.05)
        t.join(10)
        assert not t.is_alive()
        (waited,) = got
        by_req = {r[REQ]: r for r in _named("sched.submit")}
        assert by_req[free.id][COUNTS]["lock_wait_s"] < 0.005
        assert by_req[waited.id][COUNTS]["lock_wait_s"] >= 0.05
        assert waited.locked_ts - waited.submit_ts == (
            by_req[waited.id][COUNTS]["lock_wait_s"])
        assert by_req[waited.id][DUR] >= 0.05

    def test_pump_holds_the_lock_around_its_engine_step(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        sched = RequestScheduler(eng, slo=SloConfig(max_new_tokens=8))
        for p in _prompts((5, 12, 3), seed=7):
            sched.submit(p)
        sched.pump()
        assert sched._step_lat_ewma == eng.last_step_s > 0
        sched.run_to_completion()
        pumps = _named("sched.pump")
        steps = _named("engine.step")
        assert len(pumps) >= len(steps) > 0
        for step in steps:
            (pump,) = [p for p in pumps if p[ID] == step[PARENT]]
            kids = {
                r[NAME]: r for r in trace.snapshot()
                if r[PARENT] == pump[ID]
            }
            assert set(kids) == {
                "sched.admit", "engine.step", "sched.deliver",
                "sched.publish"}
            held = pump[COUNTS]["held_s"]
            assert step[DUR] <= held <= pump[DUR]
            # what the lock covers besides the step: the other spans
            assert held - step[DUR] >= (
                kids["sched.admit"][DUR] + kids["sched.deliver"][DUR]
                + kids["sched.publish"][DUR]) * (1 - 1e-9)
        admitted = sum(r[COUNTS]["admitted"] for r in _named("sched.admit"))
        assert admitted == 3
        delivered = sum(r[COUNTS]["tokens"] for r in _named("sched.deliver"))
        assert delivered == 3 * 8

    def test_metrics_render_the_legs_from_the_same_stamps(self, model):
        cfg, params = model
        metrics = ServingMetrics()
        sched = RequestScheduler(
            _engine(cfg, params), slo=SloConfig(max_new_tokens=8),
            metrics=metrics,
        )
        reqs = [sched.submit(p) for p in _prompts((5, 12, 3), seed=8)]
        sched.run_to_completion()
        text = metrics.render()
        for family in (
            "serving_sched_lock_wait_ms", "serving_queue_wait_ms",
        ):
            assert f"# TYPE {family} summary" in text
            assert f'{family}{{quantile="0.5"}}' in text
            assert f'{family}{{quantile="0.95"}}' in text
            assert f"{family}_count 3" in text
        (ratio,) = [
            float(line.split()[1]) for line in text.splitlines()
            if line.startswith("serving_sched_lock_held_ratio ")
        ]
        pumps = _named("sched.pump")
        held = sum(p[COUNTS]["held_s"] for p in pumps)
        assert 0.0 < ratio <= 1.0
        assert ratio == pytest.approx(
            held / (pumps[-1][WALL] + pumps[-1][DUR] - pumps[0][WALL]),
            rel=0.05,
        )
        waits = sorted(
            (r.admitted_ts - r.queued_ts) * 1e3 for r in reqs)
        assert f"serving_queue_wait_ms_sum {sum(waits):.6g}" in text


class TestGatewaySpan:
    def test_generate_covers_the_request_from_parse_to_answer(self, model):
        cfg, params = model
        sched = RequestScheduler(
            _engine(cfg, params), slo=SloConfig(max_new_tokens=8))
        gateway = ServingGateway(sched)
        sched.start()
        gateway.start()
        try:
            body = json.dumps({
                "tokens": _prompts((5,), seed=9)[0], "max_new": 4,
                "stream": False,
            }).encode()
            with urllib.request.urlopen(urllib.request.Request(
                gateway.addr + "/v1/generate", data=body,
                headers={"Content-Type": "application/json"},
            ), timeout=120) as resp:
                answer = json.loads(resp.read())
        finally:
            gateway.stop()
            sched.stop()
        assert answer["state"] == "done" and len(answer["tokens"]) == 4
        (door,) = _named("gateway.generate")
        (submit,) = _named("sched.submit")
        assert door[REQ] == submit[REQ] == answer["id"]
        assert submit[PARENT] == door[ID]
        (first,) = [
            r for r in _named("request") if r[COUNTS]["t_end"] is None
        ]
        # the answer is written after the last token: the door's span
        # outlasts the request's time to its first token
        assert door[DUR] >= (
            first[COUNTS]["t_first"] - first[COUNTS]["t_submit"])


class TestCheckpointLegs:
    def test_a_save_and_a_restore_leave_their_legs(self, tmp_path, caplog):
        from dlrover_tpu.common.log import default_logger

        default_logger.addHandler(caplog.handler)  # it does not propagate
        ckpt = Checkpointer(
            str(tmp_path / "ckpt"), job_name=f"trace_{time.time_ns()}")
        state = {"w": jnp.arange(4096, dtype=jnp.float32), "step": 3}
        try:
            with caplog.at_level("INFO"):
                blocked = ckpt.save_checkpoint(1, state, StorageType.MEMORY)
                ckpt.save_checkpoint(2, state, StorageType.MEMORY)
                step, restored = ckpt.load_checkpoint(target=state)
        finally:
            default_logger.removeHandler(caplog.handler)
            ckpt.close()
        assert step == 2
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(state["w"]))
        saves = _named("ckpt.save")
        assert [r[COUNTS] for r in saves] == [
            {"step": 1, "first": 1}, {"step": 2, "first": 0}]
        assert blocked == saves[0][DUR]  # the span IS the blocking time
        for save in saves:
            kids = {
                r[NAME]: r for r in trace.snapshot()
                if r[PARENT] == save[ID]
            }
            assert set(kids) == {
                "ckpt.flatten", "ckpt.shm_write", "ckpt.notify"}
            (d2h,) = [
                r for r in _named("ckpt.d2h")
                if r[PARENT] == kids["ckpt.flatten"][ID]
            ]
            assert d2h[COUNTS]["leaves"] == 2
            assert kids["ckpt.shm_write"][COUNTS]["bytes"] >= 4096 * 4
            assert sum(k[DUR] for k in kids.values()) <= save[DUR]
        assert saves[0][ID] in {
            r[PARENT] for r in _named("ckpt.shm_write")
            if r[COUNTS].get("new_segment") == 1
        }
        (restore,) = _named("ckpt.restore")
        legs = {r[NAME] for r in trace.snapshot() if r[PARENT] == restore[ID]}
        assert legs == {"ckpt.shm_read", "ckpt.h2d"}
        lines = [
            r.getMessage() for r in caplog.records
            if r.getMessage().startswith("flash checkpoint")
        ]
        assert len(lines) == 3
        assert "save step 1" in lines[0] and "first_save=1" in lines[0]
        for leg in ("d2h=", "flatten=", "shm_write=", "notify="):
            assert leg in lines[0] and leg in lines[1]
        assert "restore step 2" in lines[2]
        assert "shm_read=" in lines[2] and "h2d=" in lines[2]

    def test_a_compile_record_under_a_leg_is_not_a_leg(self, caplog):
        """A restore that compiles (a respawned worker's first) still
        logs its line: jax's stretch stays in the leg's own time."""
        from dlrover_tpu.common.log import default_logger
        from dlrover_tpu.trainer.flash_checkpoint import engine as flash

        with trace.span("ckpt.restore") as sp:
            with trace.span("ckpt.h2d", bytes=64) as h2d:
                trace.record("compile", time.time(), 0.25, leg="backend",
                             program="jit(_put)", cache="off")
        default_logger.addHandler(caplog.handler)
        try:
            with caplog.at_level("INFO"):
                flash._log_legs("restore", 3, sp)
        finally:
            default_logger.removeHandler(caplog.handler)
        (line,) = [r.getMessage() for r in caplog.records
                   if r.getMessage().startswith("flash checkpoint")]
        assert "restore step 3" in line and "64 bytes" in line
        assert f"h2d={h2d.dur_s * 1e3:.1f}ms" in line
        assert "compile" not in line


def _legs(program, records=None):
    """The `compile` records of one program, by jax's name for it
    with or without the `jit(...)` around it."""
    return [
        r for r in _named("compile", records)
        if r[COUNTS]["program"] in (program, f"jit({program})")
    ]


class TestRecord:
    def test_record_leaves_the_tuple_a_span_would(self):
        with trace.span("outer") as sp:
            trace.record("timed.elsewhere", 123.5, 0.25, 7, leg="x", n=3)
        trace.record("alone", 124.0, 0.0)
        (rec,) = _named("timed.elsewhere")
        (outer,) = _named("outer")
        assert type(rec) is tuple and len(rec) == len(outer) == 7
        assert rec[WALL] == 123.5 and rec[DUR] == 0.25 and rec[REQ] == 7
        assert rec[PARENT] == sp.id and rec[COUNTS] == {"leg": "x", "n": 3}
        assert rec[ID] not in (0, sp.id)
        (alone,) = _named("alone")
        assert alone[PARENT] == 0 and alone[REQ] is None
        # a count may carry any name, the record's own fields' too
        trace.record("named", 1.0, 2.0, None, name="n", wall=3.0, dur_s=4.0)
        assert _named("named")[0][COUNTS] == {
            "name": "n", "wall": 3.0, "dur_s": 4.0}
        with pytest.raises(TypeError):
            trace.record("bad", 1.0, 2.0, None, arr=np.zeros(2))


class TestCompileRecords:
    def test_watching_twice_registers_once(self):
        from jax._src import monitoring

        assert trace.watch_compiles() and trace.watch_compiles()
        for listeners, mine in (
            (monitoring.get_event_time_span_listeners(), trace._on_leg),
            (monitoring.get_event_listeners(), trace._on_cache),
            (monitoring.get_scalar_listeners(), trace._on_leg_open),
        ):
            assert listeners.count(mine) == 1

    def test_a_first_call_leaves_three_legs_and_a_second_none(self):
        trace.watch_compiles()

        @jax.jit
        def first_call_probe(x):
            return x * 3 + 1

        before = trace.compiled()
        with trace.span("caller") as sp:
            first_call_probe(jnp.ones(5)).block_until_ready()
        legs = _legs("first_call_probe")
        assert sorted(r[COUNTS]["leg"] for r in legs) == [
            "backend", "lower", "trace"]
        assert all(r[PARENT] == sp.id and r[DUR] > 0 for r in legs)
        (backend,) = [r for r in legs if r[COUNTS]["leg"] == "backend"]
        assert backend[COUNTS]["cache"] in ("hit", "miss", "off")
        assert all(
            "cache" not in r[COUNTS] for r in legs if r is not backend)
        # the legs lie inside the span that caused them, on its clock
        (caller,) = _named("caller")
        assert all(
            caller[WALL] <= r[WALL]
            and r[WALL] + r[DUR] <= caller[WALL] + caller[DUR] + 1e-3
            for r in legs
        )
        seconds, programs = trace.compiled()
        assert programs - before[1] >= 1
        assert seconds - before[0] >= max(r[DUR] for r in legs)
        trace.clear()
        first_call_probe(jnp.ones(5)).block_until_ready()
        assert _named("compile") == []
        assert trace.compiled() == (seconds, programs)

    def test_nested_jits_give_a_union_shorter_than_the_sum(self):
        trace.watch_compiles()

        @jax.jit
        def nested_inner_probe(x):
            return jnp.tanh(x) * 2

        @jax.jit
        def nested_outer_probe(x):
            return nested_inner_probe(x) + 1

        before = trace.compiled()[0]
        nested_outer_probe(jnp.ones(5)).block_until_ready()
        (inner,) = [
            r for r in _legs("nested_inner_probe")
            if r[COUNTS]["leg"] == "trace"
        ]
        (outer,) = [
            r for r in _legs("nested_outer_probe")
            if r[COUNTS]["leg"] == "trace"
        ]
        assert outer[WALL] <= inner[WALL]
        assert inner[WALL] + inner[DUR] <= outer[WALL] + outer[DUR]
        totals = trace.compile_totals()
        not_backend = [
            r for r in _named("compile") if r[COUNTS]["leg"] != "backend"]
        assert totals["trace_lower_s"] <= (
            sum(r[DUR] for r in not_backend) - inner[DUR] + 1e-9)
        # the thread's running total counts outermost legs only
        outermost = trace.compiled()[0] - before
        assert outermost < sum(r[DUR] for r in _named("compile"))
        assert outermost >= outer[DUR]

    @pytest.mark.parametrize("event, kw", [
        ("/jax/core/compile/backend_compile_duration", {}),
        ("/jax/core/compile/jaxpr_trace_duration", {"fun_name": None}),
        ("/jax/some/other/duration", {"fun_name": "f"}),
    ], ids=["no_name", "odd_name", "other_event"])
    def test_the_listener_takes_what_jax_hands_it(self, event, kw):
        trace._on_leg_open(event, 10.0, **kw)
        trace._on_cache("/jax/compilation_cache/other", **kw)
        trace._on_leg(event, 10.0, 10.5, **kw)
        records = _named("compile")
        if "other" in event:
            assert records == []
            return
        (rec,) = records
        assert rec[WALL] == 10.0 and rec[DUR] == 0.5
        assert type(rec[COUNTS]["program"]) is str
        assert ("cache" in rec[COUNTS]) == ("backend" in event)

    def test_totals_union_overlaps_and_count_only_slow_misses(self):
        def leg(kind, start, dur, program="jit(p)", **counts):
            trace.record(
                "compile", start, dur, leg=kind, program=program, **counts)

        leg("trace", 100.0, 4.0, program="p")
        leg("trace", 101.0, 1.0, program="helper")  # inside the first
        leg("lower", 103.5, 1.5)  # overlaps its end
        leg("backend", 105.0, 2.0, cache="miss")
        leg("backend", 108.0, 0.5, program="jit(add)", cache="miss")
        leg("backend", 109.0, 3.0, program="jit(q)", cache="hit")
        leg("backend", 113.0, 1.5, program="jit(r)", cache="off")
        with trace.span("engine.step"):
            pass
        totals = trace.compile_totals()
        assert totals["trace_lower_s"] == pytest.approx(5.0)
        assert totals["backend_s"] == pytest.approx(7.0)
        assert (totals["programs"], totals["cache_hits"],
                totals["cache_misses"]) == (4, 1, 1)
        assert totals["slowest"] == "p" and totals["first_wall"] == 100.0
        cut = trace.compile_totals(since=104.0, until=108.5)
        assert cut["backend_s"] == pytest.approx(2.5)
        assert cut["trace_lower_s"] == 0.0 and cut["programs"] == 2
        assert trace.compile_totals(since=200.0) is None

    def test_totals_are_none_on_an_empty_and_on_a_full_ring(self):
        assert trace.compile_totals() is None
        trace.record("compile", 5.0, 1.0, leg="backend", program="p",
                     cache="off")
        assert trace.compile_totals()["programs"] == 1
        for _ in range(trace.RING_SIZE - 1):
            trace.event("filler")
        # full, and the oldest record is still the first: nothing lost
        assert len(trace.snapshot()) == trace.RING_SIZE
        assert trace.compile_totals(since=5.0)["programs"] == 1
        assert trace.compile_totals() is None  # does not reach back to 0
        trace.event("one more")
        assert trace.compile_totals(since=5.0) is None

    def test_a_second_compile_of_one_program_reads_the_cache(self, tmp_path):
        from jax.experimental.compilation_cache import (
            compilation_cache as cc,
        )

        trace.watch_compiles()
        names = (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
        old = [getattr(jax.config, n) for n in names]

        def make():
            @jax.jit
            def cached_twice_probe(x):
                return jnp.cos(x) * 5 - 2

            return cached_twice_probe

        try:
            for n, v in zip(names, (str(tmp_path), 0.0, 0)):
                jax.config.update(n, v)
            cc.reset_cache()
            make()(jnp.ones(9)).block_until_ready()
            (cold,) = [
                r for r in _legs("cached_twice_probe")
                if r[COUNTS]["leg"] == "backend"
            ]
            assert cold[COUNTS]["cache"] == "miss"
            # too fast to be worth storing: says nothing, counts nothing
            assert cold[DUR] < trace.STORED_COMPILE_S
            assert trace.compile_totals()["cache_misses"] == 0
            trace.clear()
            # the same program from a function jax has not seen
            make()(jnp.ones(9)).block_until_ready()
            (warm,) = [
                r for r in _legs("cached_twice_probe")
                if r[COUNTS]["leg"] == "backend"
            ]
            assert warm[COUNTS]["cache"] == "hit"
            totals = trace.compile_totals()
            assert totals["cache_hits"] >= 1 and totals["cache_misses"] == 0
        finally:
            for n, v in zip(names, old):
                jax.config.update(n, v)
            cc.reset_cache()


class TestEngineCompiles:
    """One engine a class, over a configuration of its own (no other
    test's programs are its programs), warmed on its smallest bucket."""

    @pytest.fixture(scope="class")
    def warm(self, model):
        cfg, params = model
        cfg = dataclasses.replace(cfg, norm_eps=cfg.norm_eps * 1.5)
        metrics = ServingMetrics()
        eng = _engine(cfg, params)
        sched = RequestScheduler(
            eng, slo=SloConfig(max_new_tokens=8), metrics=metrics)
        sched.submit(_prompts((5,), seed=11)[0])
        sched.run_to_completion()
        return eng, sched, metrics, trace.snapshot()

    def test_the_build_is_a_span_with_its_counts(self, warm):
        eng, _, _, records = warm
        (build,) = _named("engine.build", records)
        assert build[PARENT] == 0
        assert build[COUNTS]["slots"] == eng.n_slots == 2
        assert build[COUNTS]["max_len"] == eng.max_len == 64
        assert 0.0 <= build[COUNTS]["compile_s"] <= build[DUR]
        # the warm request compiled under the steps that met its shapes
        steps = {r[ID]: r for r in _named("engine.step", records)}
        under = {r[ID]: r for r in records if r[PARENT] in steps}
        caused = [
            r for r in _named("compile", records)
            if r[COUNTS]["leg"] == "backend" and r[PARENT] in under
        ]
        assert {under[r[PARENT]][NAME] for r in caused} >= {
            "engine.admit", "engine.dispatch"}

    def test_an_unmet_bucket_compiles_under_its_admission(self, warm):
        eng, sched, _, _ = warm
        sched.submit(_prompts((20,), seed=12)[0])  # bucket 32: not met
        sched.run_to_completion()
        steps = _named("engine.step")
        (admit,) = _named("engine.admit")
        assert admit[COUNTS]["bucket"] == 32
        backend = [
            r for r in _named("compile") if r[COUNTS]["leg"] == "backend"]
        assert backend and all(r[PARENT] == admit[ID] for r in backend)
        first = steps[0]
        assert admit[PARENT] == first[ID]
        assert first[COUNTS]["compile_s"] > 0.0
        assert first[COUNTS]["compile_s"] == pytest.approx(
            trace.compile_totals()["trace_lower_s"]
            + trace.compile_totals()["backend_s"], rel=0.05)
        assert first[COUNTS]["compile_s"] <= first[DUR]
        assert len(steps) > 1
        assert all(s[COUNTS]["compile_s"] == 0.0 for s in steps[1:])
        # again, now warm: no record, and no step pays
        trace.clear()
        sched.submit(_prompts((21,), seed=13)[0])
        sched.run_to_completion()
        assert _named("compile") == []
        assert all(
            s[COUNTS]["compile_s"] == 0.0 for s in _named("engine.step"))

    def test_metrics_render_the_engines_compile_totals(self, warm):
        eng, sched, metrics, records = warm
        sched.pump()  # a pump with no work still publishes the totals
        stats = eng.step_stats()
        assert stats["compilations"] >= 2 and stats["compile_s"] > 0.0
        text = metrics.render()
        assert "# TYPE serving_compilations_total counter" in text
        assert "# TYPE serving_compile_seconds_total counter" in text
        assert (
            f"serving_compilations_total {int(stats['compilations'])}"
            in text)
        assert (
            f"serving_compile_seconds_total {stats['compile_s']:.6g}"
            in text)
        # the same totals the spans carry: the build's and every step's
        (build,) = _named("engine.build", records)
        assert stats["compile_s"] >= build[COUNTS]["compile_s"] + sum(
            r[COUNTS]["compile_s"] for r in _named("engine.step", records)
        ) - 1e-9


class TestStartUpLine:
    def test_runtime_init_is_a_span(self, monkeypatch):
        from dlrover_tpu import runtime

        monkeypatch.delenv("DLROVER_TPU_COORDINATOR_ADDR", raising=False)
        monkeypatch.delenv("DLROVER_TPU_MASTER_ADDR", raising=False)
        runtime.init(num_processes=1, process_id=0, membership_watch=False)
        (joined,) = _named("runtime.init")
        assert joined[COUNTS] == {"nodes": 1} and joined[PARENT] == 0

    def test_the_trainer_logs_one_line_from_the_ring(self, caplog):
        from dlrover_tpu.common.log import default_logger
        from dlrover_tpu.trainer.trainer import Trainer

        default_logger.addHandler(caplog.handler)  # it does not propagate
        try:
            with caplog.at_level("INFO"):
                Trainer._log_startup()  # no compile record: no line
                with trace.span("runtime.init", nodes=1):
                    pass
                trace.record("compile", 50.0, 2.0, leg="trace", program="step")
                trace.record("compile", 52.0, 3.0, leg="backend",
                             program="jit(step)", cache="hit")
                Trainer._log_startup()
        finally:
            default_logger.removeHandler(caplog.handler)
        lines = [
            r.getMessage() for r in caplog.records
            if r.getMessage().startswith("worker start-up")
        ]
        assert len(lines) == 1
        assert "traced and lowered 2.0 s, compiled 3.0 s" in lines[0]
        assert "1 programs, 1 cache hits, 0 misses, slowest step" in lines[0]
