"""The trace reduction on a small synthetic XSpace: device-op union,
busy and idle time inside the window, per-op sums, and idle gaps
attributed to the benchmark's host spans."""
import pytest

from bench import trace

# times in microseconds; the profiler stores event offsets in picoseconds
DEVICE = [("fusion.1", 5, 25), ("spmm", 30, 50), ("spmm", 45, 55),
          ("fusion.2", 90, 120), ("early", -20, -10)]
HOST = [("bench.window", 0, 100), ("bench.decode_step", 10, 20),
        ("bench.sync", 20, 60), ("python", 0, 100)]


def xspace(device, host, t0_ns=1_000_000):
    names = sorted({n for n, *_ in device + host})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())

    def events(evs):
        return "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {(s + 50) * 10**6} "
            f"duration_ps: {(e - s) * 10**6} }}\n" for n, s, e in evs)
    # the line timestamp is 50 us before the window opens
    base = t0_ns - 50_000
    return (f'planes {{ id: 1 name: "/device:TPU:0"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: {base}\n'
            f'{events(device)} }}\n{meta} }}\n'
            f'planes {{ id: 2 name: "/host:CPU"\n'
            f'lines {{ id: 2 name: "python" timestamp_ns: {base}\n'
            f'{events(host)} }}\n{meta} }}\n')


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(xspace(DEVICE, HOST))
    return trace.summarize(trace.planes_from_profile(pd))


def test_union_merges_overlaps():
    assert trace.union([(30, 50), (5, 25), (45, 55)]) == [(5, 25), (30, 55)]


def test_busy_and_window(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(100e-6)
    # [5,25] + [30,55] + [90,100]: the last op is clipped to the window
    assert summary.busy_s == pytest.approx(55e-6)
    assert summary.idle_share == pytest.approx(0.45)


def test_per_op_device_time(summary):
    assert summary.op_s["spmm"] == pytest.approx(30e-6)
    # an op counts whole in the window it starts in
    assert summary.op_s["fusion.2"] == pytest.approx(30e-6)
    assert "early" not in summary.op_s


def test_gaps_attributed_to_innermost_bench_span(summary):
    # gap [25,30] lies inside bench.sync; [0,5] and [55,90] in no bench span
    assert summary.idle_by_span["bench.sync"] == pytest.approx(5e-6)
    assert summary.idle_by_span["untraced host"] == pytest.approx(40e-6)
    b = summary.breakdown()
    assert b["device_ops"][0][0] == "spmm"
    assert b["idle_gaps"][0] == ["untraced host", pytest.approx(40e-6)]


def test_window_span_is_required():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(xspace(DEVICE, HOST[1:]))
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(trace.planes_from_profile(pd))


KERNEL = ('%closed_call.57 = f32[8,8192]{1,0:T(8,128)S(1)} custom-call(bf16[8,2048]'
          '{1,0:T(8,128)(2,1)S(1)} %select_bitcast_fusion.15, bf16[16,8192,88]'
          '{2,1,0:T(8,128)(2,1)} %bitcast.183, s32[16,8192,88]{2,1,0:T(8,128)} '
          '%bitcast.180), custom_call_target="tpu_custom_call", operand_layout_'
          'constraints={bf16[8,2048]{1,0}, bf16[16,8192,88]{2,1,0}, s32[16,8192,'
          '88]{2,1,0}}, frontend_attributes={kernel_metadata={}}')
FUSION = ('%constant_dynamic-slice_fusion.53 = s32[1,16,8192,88]{2,3,1,0:T(8,128)'
          'S(1)} fusion(s32[16,16,8192,88]{2,3,1,0:T(8,128)} %get-tuple-element.'
          '840, s32[]{:T(128)} %x), kind=kLoop, calls=%fused_computation.1')


def test_nested_ops_count_their_self_time():
    evs = [trace.Event("while", 0, 100), trace.Event("a", 10, 30),
           trace.Event("b", 40, 90), trace.Event("c", 50, 60)]
    assert trace.self_times(evs) == [30, 20, 40, 10]


def test_balanced_spmm_kernels_are_recognised():
    from bench.metrics import is_balanced_spmm
    assert is_balanced_spmm(KERNEL)
    assert not is_balanced_spmm(FUSION)
    assert not is_balanced_spmm(KERNEL.replace("s32[", "bf16["))
    assert trace.short_name(KERNEL) == \
        "%closed_call.57 = f32[8,8192] custom-call"
    assert trace.short_name("%while.4 = (s32[]) while(x)") == "%while.4"
