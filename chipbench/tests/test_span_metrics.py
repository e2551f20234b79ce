"""The per-layer metrics that read the program's spans print a number in
a traced CPU rehearsal of a study cell."""
from test_rehearsal import copy, run_cell  # noqa: F401  (fixture, helper)

SPAN_METRICS = {"broker_ms_per_task", "engine_queue_ms", "launch_ms",
                "host_sync_ms", "bundle_write_ms", "mark_ms_per_task"}


def test_span_metrics_read_a_number(copy):  # noqa: F811
    metrics = run_cell(copy, "jag_tiny.b64", trace=1)["metrics"]
    assert SPAN_METRICS <= set(metrics), sorted(metrics)
    for name in SPAN_METRICS:
        m = metrics[name]
        assert m["unit"] == "ms" and m["value"] > 0, (name, m)
