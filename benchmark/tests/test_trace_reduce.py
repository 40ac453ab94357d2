"""The reduction from a trace to numbers, on a trace recorded on a v5e
(data/probe_v5e.xplane.pb, 200 KB: three rounds of a flash fwd+bwd
program, a paged decode program at 16 lanes and a paged chunk program,
with bench/ host spans around them) and on hand-made events for what one
chip cannot show: collectives."""
import os

import pytest

from benchmark import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data", "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(tr.load(TRACE))


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.length([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert tr.subtract([(0, 2)], []) == [(0, 2)]


def test_recorded_trace_planes_and_spans():
    trace = tr.load(TRACE)
    assert list(trace.devices) == [0]
    dev = trace.devices[0]
    assert len(dev.modules) == 15 and len(dev.ops) == 180
    names = [s.name for s in trace.spans]
    assert names.count("bench/step") == 3
    assert names.count("bench/idle_wait") == 3


def test_busy_union_and_idle_share(summary):
    # the window is first to last device event: 62.95 ms, of which the
    # device ran an instruction for 19.49 ms
    assert summary["window_s"] == pytest.approx(0.062952, abs=1e-6)
    assert summary["busy_s"] == pytest.approx(0.019491, abs=1e-6)
    assert 0 < summary["busy_s"] < summary["window_s"]
    # busy is a union: never more than the programs' own spans
    assert summary["busy_s"] <= sum(sum(v) for v in
                                    summary["module_s"].values()) + 1e-9


def test_kernel_time_by_class_and_program(summary):
    k = summary["kernel_s"]
    # three executions each; device microseconds read off the trace
    assert k["paged_attention"] == pytest.approx(13.832e-3, rel=1e-3)
    assert k["flash_fwd"] == pytest.approx(0.971e-3, rel=1e-2)
    assert k["flash_bwd_dkv"] == pytest.approx(1.112e-3, rel=1e-2)
    assert k["flash_bwd_dq"] == pytest.approx(0.795e-3, rel=1e-2)
    by = summary["kernel_by_module"]
    assert set(by["probe_train"]) == {"flash_fwd", "flash_bwd_dkv",
                                      "flash_bwd_dq"}
    assert set(by["probe_decode"]) == {"paged_attention"}
    assert by["probe_decode"]["paged_attention"] + \
        by["probe_chunk"]["paged_attention"] == \
        pytest.approx(k["paged_attention"])
    assert len(summary["module_s"]["probe_decode"]) == 3
    assert summary["module_s"]["probe_decode"][0] == \
        pytest.approx(4.636e-3, rel=1e-3)


def test_idle_is_attributed_to_the_host_spans(summary):
    idle = summary["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6)
    # three 10 ms sleeps under bench/idle_wait: the largest idle item
    assert idle["bench/idle_wait"] == pytest.approx(0.0316, abs=1e-3)
    assert summary["breakdown"]["idle_gaps"][0][0] == "bench/idle_wait"
    assert summary["breakdown"]["device_ops"][0][0] == "paged_attention"
    assert len(summary["breakdown"]["device_ops"]) <= 10


def test_kernel_class_reads_operands_not_names():
    paged = ('%anything.7 = f32[16,12,1,64]{3,2,1,0} custom-call('
             's32[16,64]{1,0} %a, s32[16]{0} %b, s32[1,1]{1,0} %c, '
             'f32[16,12,1,64]{3,2,1,0} %q, bf16[1025,12,16,64]{3,2,1,0} %k, '
             'bf16[1025,12,16,64]{3,2,1,0} %v), '
             'custom_call_target="tpu_custom_call", operand_layout=x')
    assert tr.kernel_class(paged) == "paged_attention"
    fwd = ('%jvp__.1 = (bf16[4,1024,768]{2,1,0}, f32[24,2,1024]{2,1,0}) '
           'custom-call(bf16[4,1024,768]{2,1,0} %q, bf16[4,1024,768]{2,1,0} '
           '%k, bf16[4,1024,768]{2,1,0} %v), '
           'custom_call_target="tpu_custom_call"')
    assert tr.kernel_class(fwd) == "flash_fwd"
    # as many operands as a flash kernel is not enough to be taken for one
    assert tr.kernel_class(
        '%other.1 = bf16[4,8]{1,0} custom-call(bf16[4,8]{1,0} %a, '
        'bf16[4,8]{1,0} %b, bf16[4,8]{1,0} %c), '
        'custom_call_target="tpu_custom_call"') == "pallas_other"
    assert tr.kernel_class(
        '%other.2 = bf16[4,8]{1,0} custom-call(bf16[4,8]{1,0} %a, '
        'bf16[4,8]{1,0} %b, bf16[4,8]{1,0} %c, bf16[4,8]{1,0} %d, '
        's32[4]{0} %e, s32[4]{0} %f), '
        'custom_call_target="tpu_custom_call"') == "pallas_other"
    dq = ('%jvp__.9 = bf16[4,1024,768]{2,1,0} custom-call('
          + 'bf16[4,1024,768]{2,1,0:T(8,128)(2,1)S(1)} %x, ' * 4
          + 'f32[48,2,1024]{2,1,0} %lse, f32[48,2,1024]{2,1,0} %delta), '
          'custom_call_target="tpu_custom_call"')
    assert tr.kernel_class(dq) == "flash_bwd_dq"
    assert tr.kernel_class(dq.replace(
        '= bf16[4,1024,768]{2,1,0} custom',
        '= (bf16[4,1024,768]{2,1,0}, bf16[4,1024,768]{2,1,0}) custom')) \
        == "flash_bwd_dkv"
    assert tr.kernel_class('%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), '
                           'kind=kLoop') is None
    assert tr.kernel_class('%custom-call = bf16[4]{0} custom-call(bf16[4]{0}'
                           ' %x), custom_call_target="ConcatBitcast"') is None
    assert tr.parse_hlo('%convolution_add_fusion.12 = bf16[8,8]{1,0} '
                        'fusion(bf16[8,8]{1,0} %p)') == \
        ("convolution_add_fusion", "fusion")
    assert tr.module_name("jit_decode_wave(123456)") == "decode_wave"


def _op(name, opcode, start, dur):
    return tr.Event(f"%{name} = bf16[8]{{0}} {opcode}(bf16[8]{{0}} %p)",
                    start, dur)


def test_exposed_collective_time_on_hand_made_events():
    """Chip 0: an all-reduce runs 100..200 alone (exposed 100); an async
    all-gather is in flight 300..500 while a fusion runs 350..450 (exposed
    100 of 200); its -start and -done are instants. Chip 1: the same
    without the fusion (exposed 300). Window 0..1000."""
    def chip(with_fusion):
        ops = [_op("fusion.1", "fusion", 0, 100),
               _op("all-reduce.1", "all-reduce", 100, 100),
               _op("all-gather-start.1", "all-gather-start", 300, 1),
               _op("all-gather-done.1", "all-gather-done", 499, 1)]
        if with_fusion:
            ops.append(_op("fusion.2", "fusion", 350, 100))
        return tr.DeviceLines(
            ops, [tr.Event("jit__step(1)", 0, 500)],
            [_op("all-gather-start.1", "all-gather-start", 300, 200)])
    trace = tr.Trace({0: chip(True), 1: chip(False)},
                     [tr.Event("bench/window", 0, 1000),
                      tr.Event("bench/step", 0, 600)])
    s = tr.summarize(trace)
    assert s["chips"] == 2 and s["window_s"] == pytest.approx(1000e-9)
    assert s["collective_s"] == pytest.approx(300e-9)        # per chip
    assert s["exposed_collective_s"] == pytest.approx((200 + 300) / 2 * 1e-9)
    # busy: chip 0 0..200, 300..301, 350..450, 499..500; chip 1 no fusion.2
    assert s["busy_s"] == pytest.approx((302 + 202) / 2 * 1e-9)
    assert s["idle_by_span"]["unattributed"] == pytest.approx(400e-9)
    assert s["module_s"] == {"_step": [pytest.approx(500e-9)]}


def test_a_loop_is_not_counted_beside_its_body_and_hides_no_collective():
    """A `while` runs 0..1000 and encloses, on the same line, a fusion
    0..300, an all-reduce 300..700 that nothing overlaps, and a fusion
    700..1000. The loop's own event is neither device time of its own nor
    "something else running": the all-reduce is exposed for all of its
    400, and the ranked operations add up to the busy time."""
    ops = [_op("while.7", "while", 0, 1000),
           _op("fusion.1", "fusion", 0, 300),
           _op("all-reduce.2", "all-reduce", 300, 400),
           _op("fusion.3", "fusion", 700, 300),
           _op("conditional.1", "conditional", 1000, 100),
           _op("copy.4", "copy", 1000, 100)]
    trace = tr.Trace(
        {0: tr.DeviceLines(ops, [tr.Event("jit__step(1)", 0, 1100)], [])},
        [tr.Event("bench/window", 0, 1100)])
    s = tr.summarize(trace)
    assert s["busy_s"] == pytest.approx(1100e-9)
    assert s["collective_s"] == pytest.approx(400e-9)
    assert s["exposed_collective_s"] == pytest.approx(400e-9)
    assert set(s["op_s"]) == {"fusion", "all-reduce", "copy"}
    assert sum(s["op_s"].values()) == pytest.approx(s["busy_s"])
    assert [k for k, _ in s["breakdown"]["device_ops"]] == \
        ["fusion", "all-reduce", "copy"]


# ------------------------------------------------- the program's own spans
SERVING = os.path.join(os.path.dirname(__file__), "data",
                       "serving_spans_v5e.xplane.pb")


def test_idle_is_named_by_the_programs_spans():
    """A trace recorded on a v5e (data/serving_spans_v5e.xplane.pb, 0.8 MB:
    three scheduler rounds of a two-layer decoder of gpt2 widths behind
    the paged engine, 4 slots, each round inside a `bench/step` and
    followed by a 4 ms `bench/idle_wait`): the program's `serving/...`
    spans are kept beside the benchmark's, each idle instant goes to the
    innermost of them, and the two stages lead the list where `bench/step`
    alone stood before."""
    trace = tr.load(SERVING)
    names = [s.name for s in trace.spans]
    assert len(names) == 44 and names.count("bench/step") == 3
    assert names.count("serving/round") == 3
    assert names.count("serving/wave/stage") == 3
    assert names.count("serving/prefill/stage") == 2
    assert all(n.startswith(tr.SPAN_PREFIXES) for n in names)
    s = tr.summarize(trace)
    assert s["window_s"] == pytest.approx(0.050205, abs=1e-6)
    assert s["busy_s"] == pytest.approx(0.001272, abs=1e-6)
    idle = s["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"],
                                               rel=1e-9)
    assert idle["serving/wave/stage"] == pytest.approx(14.50e-3, abs=1e-5)
    assert idle["serving/prefill/stage"] == pytest.approx(10.53e-3, abs=1e-5)
    assert idle["serving/wave/wait"] == pytest.approx(5.56e-3, abs=1e-5)
    assert idle["bench/idle_wait"] == pytest.approx(12.65e-3, abs=1e-5)
    # what the rounds' own spans do not cover is all that is left to the
    # benchmark's span around them
    assert idle["bench/step"] < 0.2e-3 and idle["unattributed"] < 0.1e-3
    assert [k for k, _ in s["breakdown"]["idle_gaps"][:3]] == [
        "serving/wave/stage", "bench/idle_wait", "serving/prefill/stage"]
    # the readers that look programs up by name read as before
    assert len(s["module_s"]["decode_wave"]) == 3
    assert len(s["module_s"]["prefill_chunk"]) == 2
    assert set(s["kernel_by_module"]["decode_wave"]) == {"paged_attention"}


def test_span_families_kept_and_the_runtimes_own_events_dropped():
    keep = ["bench/step", "serving/wave/wait", "train", "train/stage",
            "collective/all_reduce"]
    drop = ["PjitFunction(decode_wave)", "TfrtCpuExecutable::Execute",
            "$core.py:123 bind", "Thread pool"]
    assert all(n.startswith(tr.SPAN_PREFIXES) for n in keep)
    assert not any(n.startswith(tr.SPAN_PREFIXES) for n in drop)


def test_innermost_span_wins_across_threads_and_ties():
    """Hand-made: a round of 100 with two children, a span of another
    thread across the first child's end, and device work under part of
    it. Every instant goes to the shortest span that covers it."""
    ev = tr.Event
    spans = [ev("bench/window", 0, 100), ev("serving/round", 0, 100),
             ev("serving/wave/stage", 10, 20),      # 10..30
             ev("serving/wave/wait", 30, 60),       # 30..90
             ev("bench/poll", 25, 10)]              # 25..35, another thread
    busy = [(40, 80)]
    idle = tr.idle_by_span(busy, spans, 0, 100)
    assert idle == {"serving/round": 20,            # 0..10 and 90..100
                    "serving/wave/stage": 15,       # 10..25
                    "bench/poll": 10,               # 25..35: shortest there
                    "serving/wave/wait": 15}        # 35..40 and 80..90
    assert sum(idle.values()) == 100 - 40
    assert tr.idle_by_span([], [], 0, 10) == {"unattributed": 10}
    assert tr.idle_by_span([], spans, 5, 5) == {}
