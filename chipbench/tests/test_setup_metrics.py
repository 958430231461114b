"""The set-up metrics (PR 36), added as files and entries alone: each is an
entry of ``BENCHMARK.json``, a file of ``layer_metrics/`` and a reader that
``readers.find`` finds; the five parts and ``setup_rest_ms`` add up to
``setup_s``; no reader file imports the program before the TPU runtime's
start; and on a program that lacks the spans every reader returns None."""
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, readers                    # noqa: E402

PARTS = ["runtime_start_ms", "program_import_ms", "deferred_init_ms",
         "state_init_ms", "first_step_ms"]
NEW = PARTS + ["step_trace_ms", "step_lower_ms", "step_backend_ms",
               "backend_compile_ms_total", "cache_hit_pct", "setup_rest_ms"]
SPANS = {"deferred_init_ms": "TrainStep.deferred_init",
         "state_init_ms": "TrainStep.state_init",
         "first_step_ms": "TrainStep.compile"}
# one traced run of resnet50_v1.train_b256 on the chip, warm (PR 36; seed
# 2147483659): what the program left and what the harness timed
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "setup_recorded.json")) as _f:
    RECORDED = json.load(_f)


def _spec(name):
    return manifest.load_json(ROOT, manifest.metric_file(name))


def _read(ctx, name):
    spec = dict(_spec(name)["reader"])
    return readers.find(spec.pop("fn"))(ctx, **spec)


def _ctx(counters, t0, setup_s):
    return types.SimpleNamespace(counters=dict(counters), t0=t0, reduced=None,
                                 e2e={"setup_s": setup_s}, cell={"root": ROOT})


@pytest.fixture
def recorded():
    """The recorded run as a context, its spans in the program's registry."""
    from mxnet_tpu import telemetry
    reg = telemetry.registry()
    for name, ms in RECORDED["spans_ms"].items():
        reg.remove(f"{name}_ms")
        reg.histogram(f"{name}_ms", telemetry.SPAN_MS_BUCKETS).observe(ms)
    yield _ctx(RECORDED["counters"], RECORDED["t0"], RECORDED["setup_s"])
    for name in RECORDED["spans_ms"]:
        reg.remove(f"{name}_ms")


def test_the_real_tree_validates_with_the_new_entries():
    m = manifest.load(ROOT)
    assert manifest.validate(m, ROOT) == []
    cells = [w["name"] for w in m["workloads"]]
    rows = {r["name"]: r for r in m["per_layer"]}
    assert [r["name"] for r in m["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        assert rows[name]["moves"] == "setup_s"
        assert rows[name]["workloads"] == cells
        assert rows[name]["better"] == (
            "higher" if name == "cache_hit_pct" else "lower")
    assert {rows[n]["layer"] for n in NEW} == {
        "entry_point", "train_step", "compile_cache"}


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_a_file_with_what_and_a_reader(name):
    spec = _spec(name)
    assert set(spec) == {"what", "reader"} and len(spec["what"]) > 40
    assert readers.find(spec["reader"]["fn"]) is not None
    if name in SPANS:
        assert spec["reader"] == {"fn": "span_total_ms", "name": SPANS[name]}
    assert _spec("setup_rest_ms")["reader"]["parts"] == PARTS


def test_the_parts_and_the_rest_add_up_to_setup_s(recorded):
    values = {name: _read(recorded, name) for name in NEW}
    assert all(isinstance(v, float) for v in values.values()), values
    total = sum(values[n] for n in PARTS) + values["setup_rest_ms"]
    assert total == pytest.approx(RECORDED["setup_s"] * 1e3, abs=1e-6)
    assert values["setup_rest_ms"] > 0
    assert values["step_trace_ms"] + values["step_lower_ms"] \
        + values["step_backend_ms"] <= values["first_step_ms"]
    assert 0.0 <= values["cache_hit_pct"] <= 100.0
    assert values["runtime_start_ms"] == pytest.approx(
        (RECORDED["counters"]["process.import_t0_s"] - RECORDED["t0"]) * 1e3)


def test_a_program_without_the_spans_reads_as_nothing():
    """The parent commit has no set-up span, no split of a compile and no
    import stamp: each reader returns None and raises nothing, and the two
    that read what the parent already counts still read."""
    parent = {k: v for k, v in RECORDED["counters"].items()
              if not k.startswith(("process.", "compile::TrainStep::"))}
    ctx = _ctx(parent, RECORDED["t0"], RECORDED["setup_s"])
    values = {name: _read(ctx, name) for name in NEW}
    assert {n for n, v in values.items() if v is not None} == {
        "backend_compile_ms_total", "cache_hit_pct"}


def test_no_reader_file_imports_the_program():
    """``manifest.validate`` imports every reader file before ``run.py`` asks
    for the devices; the program's import belongs after the runtime's start,
    where ``process.import_t0_s`` stamps it."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from chipbench import manifest\n"
            "assert manifest.validate(manifest.load(%r), %r) == []\n"
            "assert 'mxnet_tpu' not in sys.modules and 'jax' not in "
            "sys.modules, sorted(m for m in sys.modules if '.' not in m)"
            % (ROOT, ROOT, ROOT))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT)
