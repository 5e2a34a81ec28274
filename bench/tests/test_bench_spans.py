"""The per-layer metrics that read the program's own spans and counters:
a traced CPU rehearsal of each cell reports every one of them, and each
reader returns nothing, without raising, where the program has no such
span or counter."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import rehearsal  # noqa: E402
from benchkit import harness, spec  # noqa: E402

SEED = 2**31 + 7171
SECONDS = 3.0

METRICS = {
    "gateway-backfill": (
        "feed_parse_ms.backfill", "session_prepare_ms.backfill",
        "scan_dispatch_ms.backfill", "scan_sync_wait_ms.backfill",
        "session_commit_ms.backfill", "scan_dispatches_per_request.backfill",
        "loop_lag_ms.backfill"),
    "archive-dashboard": (
        "decode_queue_wait_ms.dashboard",
        "decode_answer_encode_ms.dashboard", "loop_lag_ms.dashboard"),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    # two channels and short feeds: several requests land in the traced
    # part even on a loaded CPU, where the kernels are interpreted
    cfg = rehearsal.tiny_config("upmu-gateway")
    cfg["channels"] = cfg["channels"][:1] + cfg["channels"][6:7]
    mix = rehearsal.tiny_mix("backfill")
    mix.update(samples_per_feed=900, backlog_minutes=120)
    yield rehearsal.make_root(tmp_path_factory.mktemp("spans"), cells={
        "gateway-backfill": ("upmu-gateway", cfg, "backfill", mix),
        "archive-dashboard": ("upmu-archive",
                              rehearsal.tiny_config("upmu-archive"),
                              "dashboard", rehearsal.tiny_mix("dashboard"))})
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", sorted(METRICS))
def test_traced_rehearsal_reports_the_program_span_metrics(root, cell):
    res = harness.run_cell(root, cell, SEED, SECONDS, True,
                           require_tpu=False)
    assert res["correct"], res["compared"]
    got = res["metrics"]
    assert set(METRICS[cell]) <= set(got)
    for name in METRICS[cell]:
        assert got[name]["value"] > 0, name
    if cell == "gateway-backfill":
        # one direct scan per line, a line per channel (the traced part's
        # edges may cut a request in two)
        channels = len(spec.load_cell(root, cell).cfg["channels"])
        per_request = got["scan_dispatches_per_request.backfill"]["value"]
        assert 0.5 * channels <= per_request <= 1.5 * channels


@pytest.mark.parametrize("name", sorted(n for names in METRICS.values()
                                        for n in names))
def test_reader_finds_nothing_without_the_programs_counters(name):
    ctx = harness.RunContext(
        cell=None, kind=None, seed=SEED, t0=0.0, t1=1.0, setup_s=1.0,
        records=[], done={}, counters_before={}, counters_after={},
        compiles_setup={}, compiles_window={}, peaks={})
    assert spec.metric_reader(BENCH.parent, name)(ctx) is None
