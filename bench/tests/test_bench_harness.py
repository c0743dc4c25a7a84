"""The harness's own arithmetic: percentiles with missing values, the
compile counter that shows no compile inside a window, and its spans."""

import math

import jax
import jax.numpy as jnp
import pytest

from bench.lib import harness


def test_nearest_rank_counts_missing_as_worst():
    v = list(range(1, 11))
    assert harness.nearest_rank(v, 0.9) == 9
    assert harness.nearest_rank(v, 0.95) == 10
    assert harness.nearest_rank(v[:9] + [math.inf], 0.95) == math.inf
    assert math.isnan(harness.nearest_rank([], 0.9))


def test_compile_counter_sees_compiles_only_while_active():
    c = harness.CompileCounter()
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones(3)).block_until_ready()  # compiled while inactive
    c.active = True
    f(jnp.ones(3)).block_until_ready()  # a cached program: no compile
    assert c.n == 0
    jax.jit(lambda x: x * 5 - 2)(jnp.ones(7)).block_until_ready()
    c.active = False
    assert c.n >= 1  # the new program (and the new shape's ones)


def test_spans_total_and_count():
    s = harness.Spans()
    for _ in range(3):
        with s("step"):
            pass
    assert s.count["step"] == 3 and s.total["step"] >= 0.0
    s.reset()
    assert not s.count


def test_result_line_puts_checks_last_and_fails_on_a_broken_limit(capsys):
    from types import SimpleNamespace

    dev = jax.devices()[0]
    ctx = SimpleNamespace(devices=[dev], trace=False)
    cell = {"end_to_end": [{"name": "setup_s", "unit": "s"}]}
    out = {"e2e": {"setup_s": 1.5}, "checks": {"gap": (0.2, 0.1)},
           "attempted": 3, "failed": 0, "memory_peak_bytes": 0}
    assert harness.emit(ctx, cell, out) == 0
    import json

    captured = capsys.readouterr()
    line = json.loads(captured.out.strip())
    assert line["correct"] is False
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["checks"]["gap"] == {"value": 0.2, "limit": 0.1}
    assert captured.err.strip().splitlines()[-1] == "check gap 0.2 limit 0.1"


@pytest.mark.parametrize("value", [math.nan, math.inf])
def test_a_metric_not_measured_is_an_error(value):
    from types import SimpleNamespace

    ctx = SimpleNamespace(devices=jax.devices()[:1], trace=False)
    cell = {"end_to_end": [{"name": "setup_s", "unit": "s"}]}
    out = {"e2e": {"setup_s": value}, "checks": {}, "attempted": 0,
           "failed": 0, "memory_peak_bytes": 0}
    with pytest.raises(RuntimeError):
        harness.emit(ctx, cell, out)
