"""The summed durations of the window's ``train.step_device`` spans over the window's length (``obs["window_s"]``) less what the profiler took to start and stop inside it (``step_records.profiler_s``: 1 to 12 s of a traced run's window): what is left is ``fit``'s boundary and the harness between calls, over the whole window and not its traced steps."""

from benchmark import step_records


def read(obs):
    if not obs.get("window_s"):
        return None
    return step_records.read(
        obs, lambda steps: sum(r["dur_ms"] for r in steps) / 1e3 / (obs["window_s"] - step_records.profiler_s(obs, steps)) * 100.0
    )
