"""Compiled-program cost profiling: cost/memory extraction, roofline verdicts,
the program catalog's gauges + compile histogram, and the derived device-occupancy
gauge.  Everything runs on the CPU backend — ``compiled.cost_analysis()`` works
there, which is exactly why the profiler can be tier-1-tested at all."""

import jax
import jax.numpy as jnp
import pytest

from nanofed_tpu.observability import (
    MetricsRegistry,
    PlatformPeaks,
    ProgramCatalog,
    ProgramCostReport,
    format_cost_table,
    peaks_for_device_kind,
    profile_program,
    update_device_occupancy,
)
from nanofed_tpu.observability.profiling import (
    DEVICE_OCCUPANCY_GAUGE,
    PROGRAM_COMPILE_HISTOGRAM,
    PROGRAM_FLOPS_GAUGE,
    PROGRAM_INTENSITY_GAUGE,
    PROGRAM_PEAK_BYTES_GAUGE,
    extract_cost_analysis,
    extract_memory_analysis,
)
from nanofed_tpu.observability.spans import SpanTracer


def _matmul_jit():
    return jax.jit(lambda x, y: (x @ y).sum() + jnp.sin(x).sum())


def test_profile_program_extracts_compiler_costs_on_cpu():
    fn = _matmul_jit()
    x = jnp.ones((64, 64))
    report = profile_program("matmul", fn, x, x)
    # XLA's numbers, not an analytic guess: a 64x64x64 matmul alone is
    # 2*64^3 = 524288 FLOPs; sin contributes transcendentals.
    assert report.flops >= 2 * 64**3
    assert report.transcendentals >= 64 * 64
    assert report.bytes_accessed > 0
    assert report.peak_bytes > 0
    assert report.arithmetic_intensity == pytest.approx(
        report.flops / report.bytes_accessed
    )
    assert report.compile_seconds > 0
    assert report.platform == "cpu"
    # CPU has no published peak: the verdict must SAY so, never fabricate one.
    assert report.peaks is None
    assert report.verdict == "no peak basis"
    assert report.lower_bound_s is None
    assert report.mfu(1.0) is None


def test_report_roofline_verdicts_against_explicit_peaks():
    fn = _matmul_jit()
    x = jnp.ones((64, 64))
    base = profile_program("m", fn, x, x)
    # Ridge = flops_per_s / bytes_per_s.  Pick peaks on either side of the
    # program's measured intensity to force both verdicts.
    ai = base.arithmetic_intensity
    compute_bound = ProgramCostReport(
        **{**base.__dict__, "peaks": PlatformPeaks(1e12, 1e12 / (ai / 2), "test")}
    )
    assert compute_bound.verdict == "compute-bound"
    memory_bound = ProgramCostReport(
        **{**base.__dict__, "peaks": PlatformPeaks(1e12, 1e12 / (ai * 2), "test")}
    )
    assert memory_bound.verdict == "memory-bound"
    # Lower bound: the slower of the two feeds, per device.
    peaks = memory_bound.peaks
    expect = max(base.flops / peaks.flops_per_s,
                 base.bytes_accessed / peaks.hbm_bytes_per_s)
    assert memory_bound.lower_bound_s == pytest.approx(expect)
    # MFU from a measured walltime, on the compiler-FLOPs basis.
    assert memory_bound.mfu(2.0) == pytest.approx(
        base.flops / 2.0 / peaks.flops_per_s
    )


def test_report_to_dict_is_json_shaped():
    fn = _matmul_jit()
    x = jnp.ones((8, 8))
    d = profile_program("p", fn, x, x, rounds=4, attrs={"k": 1}).to_dict()
    assert d["program"] == "p"
    assert d["rounds"] == 4
    assert d["flops_per_round"] == pytest.approx(d["flops"] / 4)
    assert d["verdict"] == "no peak basis"
    assert d["attrs"] == {"k": 1}
    import json

    json.dumps(d)  # must be JSON-serializable as-is (telemetry record shape)


def test_peaks_table_matches_device_kinds():
    v5e = peaks_for_device_kind("TPU v5 lite", "tpu")
    assert v5e is not None and v5e.flops_per_s == 197e12
    v5p = peaks_for_device_kind("TPU v5p", "tpu")
    assert v5p is not None and v5p.flops_per_s == 459e12
    assert peaks_for_device_kind("TPU v4", "tpu").hbm_bytes_per_s == 1228e9
    # No fabricated peaks: CPU and unknown kinds get None.
    assert peaks_for_device_kind("cpu", "cpu") is None
    assert peaks_for_device_kind("TPU v99", "tpu") is None


def test_extractors_tolerate_absence():
    class Full:
        def cost_analysis(self):
            return {"flops": 10.0, "bytes accessed": 4.0, "transcendentals": 1.0}

    class Partial:
        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 2.0}

    class Broken:
        def cost_analysis(self):
            raise RuntimeError("backend says no")

        def memory_analysis(self):
            return None

    assert extract_cost_analysis(Full()) == {
        "flops": 10.0, "transcendentals": 1.0, "bytes_accessed": 4.0
    }
    assert extract_cost_analysis(Partial())["flops"] == 7.0
    assert extract_cost_analysis(Partial())["transcendentals"] == 0.0
    # A missing analysis degrades to zeros — it must never raise.
    assert extract_cost_analysis(Broken())["flops"] == 0.0
    assert extract_memory_analysis(Broken())["peak_bytes"] == 0


def test_memory_analysis_peak_subtracts_aliased_bytes():
    class Stats:
        argument_size_in_bytes = 100
        output_size_in_bytes = 60
        temp_size_in_bytes = 40
        alias_size_in_bytes = 50  # donated buffers counted once, not twice
        generated_code_size_in_bytes = 7

    class Compiled:
        def memory_analysis(self):
            return Stats()

    mem = extract_memory_analysis(Compiled())
    assert mem["peak_bytes"] == 100 + 60 + 40 - 50
    assert mem["generated_code_bytes"] == 7


def test_catalog_registers_lazily_and_publishes_gauges():
    reg = MetricsRegistry()
    catalog = ProgramCatalog(registry=reg)
    calls = {"n": 0}

    def factory():
        calls["n"] += 1
        x = jnp.ones((16, 16))
        return (x, x), {}

    catalog.register("prog", _matmul_jit(), args_factory=factory, rounds=2)
    assert calls["n"] == 0  # registration materializes NOTHING
    assert catalog.report("prog") is None
    report = catalog.profile("prog")
    assert calls["n"] == 1
    assert report.rounds == 2
    # Cached: a second profile is free (and the factory untouched).
    assert catalog.profile("prog") is report
    assert calls["n"] == 1
    # Gauges + compile histogram landed in the registry, labeled by program.
    assert reg.gauge(PROGRAM_FLOPS_GAUGE, labels=("program",)).value(
        program="prog"
    ) == report.flops
    assert reg.gauge(PROGRAM_PEAK_BYTES_GAUGE, labels=("program",)).value(
        program="prog"
    ) == report.peak_bytes
    assert reg.gauge(PROGRAM_INTENSITY_GAUGE, labels=("program",)).value(
        program="prog"
    ) == pytest.approx(report.arithmetic_intensity)
    hist = reg.histogram(PROGRAM_COMPILE_HISTOGRAM, labels=("program",))
    assert hist.sample_count(program="prog") == 1
    # /metrics exposition: the new gauges render in Prometheus text format.
    text = reg.render_prometheus()
    assert f'{PROGRAM_FLOPS_GAUGE}{{program="prog"}}' in text
    assert f'{PROGRAM_PEAK_BYTES_GAUGE}{{program="prog"}}' in text


def test_catalog_unknown_program_and_unlowerable_fn():
    catalog = ProgramCatalog(registry=MetricsRegistry())
    with pytest.raises(KeyError, match="no program"):
        catalog.profile("nope")
    with pytest.raises(TypeError, match="not lowerable"):
        profile_program("plain", lambda x: x, 1)


def test_jit_program_attribute_is_honored():
    """A plain wrapper exposing its inner jit via .jit_program (the fused-block
    builder's shape) profiles through to the real program."""
    inner = _matmul_jit()

    def wrapper(x, y):  # pragma: no cover - never executed by the profiler
        return inner(x, y)

    wrapper.jit_program = inner
    x = jnp.ones((16, 16))
    report = profile_program("wrapped", wrapper, x, x)
    assert report.flops >= 2 * 16**3


def test_device_occupancy_from_fused_spans():
    # A fused block's segments, summed over its rounds: dispatch 1 s (prepare
    # included), device_wait 3 s.
    reg = MetricsRegistry()
    block = {"prepare": 0.25, "dispatch": 0.75, "device_wait": 3.0, "readback": 0.0}
    ratio = update_device_occupancy(block, reg)
    assert ratio == pytest.approx(0.75)
    assert reg.gauge(DEVICE_OCCUPANCY_GAUGE).value() == pytest.approx(0.75)
    # publish is host time the device spends idle — it must DILUTE the ratio,
    # or a publish-heavy run would overstate occupancy above the lower bound.
    assert update_device_occupancy({**block, "publish": 4.0}, reg) == pytest.approx(
        3.0 / 8.0
    )


def test_device_occupancy_single_round_fallback_and_empty():
    reg = MetricsRegistry()
    assert update_device_occupancy({}, reg) is None  # nothing timed yet
    # A round that FAILED before any dispatch has no device_wait: no ratio, and
    # the gauge keeps what the last dispatched round left.
    assert update_device_occupancy({"prepare": 1.0, "publish": 1.0}, reg) is None
    assert reg.gauge(DEVICE_OCCUPANCY_GAUGE).value() == 0.0
    round_ = {"prepare": 1.0, "dispatch": 0.5, "device_wait": 6.0, "readback": 0.5}
    assert update_device_occupancy(round_, reg) == pytest.approx(0.75)
    assert update_device_occupancy({**round_, "publish": 4.0}, reg) == pytest.approx(0.5)
    # The ratio is of the LAST round (block) alone: a slow first round does not
    # linger in it, as it did in the span histogram's process-lifetime sums.
    assert update_device_occupancy(
        {"prepare": 0.25, "dispatch": 0.75, "device_wait": 3.0, "publish": 4.0}, reg
    ) == pytest.approx(3.0 / 8.0)
    assert reg.gauge(DEVICE_OCCUPANCY_GAUGE).value() == pytest.approx(3.0 / 8.0)


def test_device_occupancy_ratio_is_clamped():
    # A host segment cut between two spans' readings can come out a hair
    # negative; the published ratio must stay a ratio.
    reg = MetricsRegistry()
    assert update_device_occupancy({"dispatch": -1.0, "device_wait": 2.0}, reg) == 1.0


def test_occupancy_integrates_with_real_tracer_spans():
    reg = MetricsRegistry()
    tracer = SpanTracer(registry=reg)
    with tracer.span("dispatch") as dispatch:
        pass
    with tracer.span("device-wait") as wait:
        pass
    ratio = update_device_occupancy(
        {"dispatch": wait.t_start - dispatch.t_start, "device_wait": wait.duration_s},
        reg,
    )
    assert ratio is not None and 0.0 <= ratio <= 1.0


def test_format_cost_table_shapes():
    fn = _matmul_jit()
    x = jnp.ones((8, 8))
    r = profile_program("tiny_program", fn, x, x, rounds=2)
    table = format_cost_table([r])
    assert "tiny_program" in table
    assert "flops/round" in table
    assert "no peak basis" in table  # CPU: stated, not fabricated
    with_peaks = ProgramCostReport(
        **{**r.__dict__, "peaks": PlatformPeaks(197e12, 819e9, "TPU v5e test")}
    )
    table2 = format_cost_table([with_peaks])
    assert "TPU v5e test" in table2
    assert with_peaks.verdict in table2
