"""Tracing/profiling subsystem (SURVEY.md §5: absent in reference, required here)."""

from sharetrade_tpu.utils.profiling import StepTimer


class TestStepTimer:
    def test_first_tick_is_baseline(self):
        t = StepTimer(chunk_steps=10, num_agents=4)
        assert t.tick() == {}
        m = t.tick()
        assert m["chunk_seconds"] > 0
        assert m["agent_steps_per_sec"] > 0
        assert t.summary()["chunks_timed"] == 1.0

    def test_rates_consistent(self):
        t = StepTimer(chunk_steps=100, num_agents=10)
        t.tick()
        m = t.tick()
        assert abs(m["agent_steps_per_sec"] / m["env_steps_per_sec"] - 10.0) < 1e-6


# The two ``Tracer.span`` cases (a no-op without a profiler; in the device
# trace under ``runtime.profile_dir``) are cases of the one span path now:
# tests/test_host_spans.py::test_host_span_cases.
