"""tools/xplanespans on a small trace in its plain form: busy by
program name a device plane, the first device's idle time by the
innermost span of the thread that runs a group, and the command
itself."""

import json
import os

import pytest

from bigslice_tpu.tools import xplanespans

FIXTURE = os.path.join(os.path.dirname(__file__),
                       "xplanespans_trace.json")
US = 1e-6       # the fixture's numbers are whole microseconds


@pytest.fixture(scope="module")
def plain():
    with open(FIXTURE) as fp:
        return json.load(fp)


def without(plain, prefix):
    """The trace as ``load`` gives it when ``prefix`` was not asked for."""
    planes = []
    for p in plain["planes"]:
        lines = [{"name": ln["name"],
                  "events": [e for e in ln["events"]
                             if not e[0].startswith(prefix)]}
                 for ln in p["lines"]]
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def test_busy_by_program_name_a_device_plane(plain):
    red = xplanespans.reduce(plain)
    assert list(red["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    dev0, dev1 = red["devices"].values()
    assert dev0["programs"] == {
        "jit_bs_group_shuffle": {"calls": 2,
                                 "busy_s": pytest.approx(180 * US)},
        "jit_bs_merge": {"calls": 1, "busy_s": pytest.approx(40 * US)},
        "jit_bs_prefix": {"calls": 1, "busy_s": pytest.approx(20 * US)}}
    assert list(dev0["programs"]) == ["jit_bs_group_shuffle",
                                      "jit_bs_merge", "jit_bs_prefix"]
    # The union of the op line: the 5 us between two ops of the second
    # shuffle program are idle, the program's event covers them.
    assert dev0["busy_s"] == pytest.approx(235 * US)
    assert dev1 == {"busy_s": pytest.approx(96 * US), "programs": {
        "jit_bs_group_shuffle": {"calls": 1,
                                 "busy_s": pytest.approx(96 * US)}}}
    assert red["window_s"] == pytest.approx(1200 * US)


def test_idle_goes_to_the_innermost_span_of_the_thread_that_runs_a_group(
        plain):
    idle = xplanespans.reduce(plain)["idle"]
    assert idle["device"] == "/device:TPU:0"
    assert idle["idle_s"] == pytest.approx((1200 - 235) * US)
    want = {
        # No group open: the jobs' thread, innermost first.
        "bench:run": 50 + 100, "bigslice:session.run": 10 + 10 + 20,
        "bigslice:compile_tasks": 30, "bigslice:evaluate": 20 + 20,
        "bench:scan": 20 + 20, "bigslice:readback": 80 + 60,
        # Group A alone, then beside group B: an instant with two
        # groups open is halved between their innermost spans.
        "bigslice:group": (10 + 10 + 10 + 45 + 5 + 20 + 5 + 2.5
                           + 20 + 160),
        "bigslice:stage_wait": 5 + 5,
        # Wave 0's inline stage, inside its group — never the prefetch
        # thread's, which stand beside the groups.
        "bigslice:stage": 60,
        "bigslice:dispatch": 30 + 5 + 5, "bigslice:enqueue": 60 + 20,
        "bigslice:mutex_wait": 45, "bigslice:sync.shuffle_counts": 2.5,
    }
    assert idle["by_span"] == {k: pytest.approx(v * US)
                               for k, v in want.items()}
    assert "bigslice:upload" not in idle["by_span"]
    assert "bigslice:settle" not in idle["by_span"]      # device busy
    assert list(idle["by_span"])[0] == "bigslice:group"  # largest first


def test_without_the_benchmarks_prefix_its_time_has_no_span(plain):
    red = xplanespans.reduce(without(plain, "bench:"))
    assert red["window_s"] == pytest.approx((1180 - 50) * US)
    by_span = red["idle"]["by_span"]
    assert by_span[xplanespans.NO_SPAN] == pytest.approx(120 * US)
    assert not any(k.startswith("bench:") for k in by_span)
    assert red["idle"]["idle_s"] == pytest.approx(
        (1130 - 235) * US)


def test_a_trace_without_a_device_plane_has_no_idle_table(plain):
    host = {"planes": [p for p in plain["planes"]
                       if p["name"].startswith("/host")]}
    red = xplanespans.reduce(host)
    assert red["devices"] == {} and red["idle"] is None
    with pytest.raises(ValueError, match="no host annotation"):
        xplanespans.reduce(without(without(plain, "bench:"),
                                   "bigslice:"))


def test_program_name_drops_the_fingerprint_only():
    assert xplanespans.program_name(
        "jit_bs_group_joinlookup_map_shuffle(18231094172)") == \
        "jit_bs_group_joinlookup_map_shuffle"
    assert xplanespans.program_name("jit_bs_merge") == "jit_bs_merge"
    assert xplanespans.program_name("jit_f(x)(12)") == "jit_f(x)"


def test_the_command_prints_both_tables_and_json(capsys, tmp_path):
    assert xplanespans.main([FIXTURE, "--jobs", "2"]) == 0
    out = capsys.readouterr().out
    assert "/device:TPU:0: busy" in out and "ms a job" in out
    assert "jit_bs_group_shuffle" in out and " 1 calls" in out  # a job
    assert "bigslice:enqueue" in out and "bigslice:sync.shuffle_counts" \
        in out
    dump = str(tmp_path / "plain.json")
    assert xplanespans.main([FIXTURE, "--json", "--dump", dump]) == 0
    red = json.loads(capsys.readouterr().out)
    assert red["idle"]["by_span"]["bigslice:mutex_wait"] == \
        pytest.approx(45 * US)
    with open(dump) as fp, open(FIXTURE) as want:
        assert json.load(fp)["planes"] == json.load(want)["planes"]


def test_load_reads_a_profilers_trace_of_this_process(tmp_path):
    """On the CPU there is no device plane; the program's annotations
    come through by prefix, thread by thread, and nest."""
    import jax

    from bigslice_tpu.utils.trace import span

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("group", op="x"):
            with span("dispatch", wave=0):
                with span("enqueue"):
                    pass
        with jax.profiler.TraceAnnotation("other:thing"):
            pass
    finally:
        jax.profiler.stop_trace()
    plain = xplanespans.load(str(tmp_path))
    names = [e[0] for p in plain["planes"] for ln in p["lines"]
             for e in ln["events"]]
    assert sorted(names) == ["bigslice:dispatch", "bigslice:enqueue",
                             "bigslice:group"]
    assert "other:thing" in [
        e[0] for p in xplanespans.load(
            xplanespans.newest_xplane(str(tmp_path)),
            ["bigslice:", "other:"])["planes"]
        for ln in p["lines"] for e in ln["events"]]
    red = xplanespans.reduce(plain)
    assert red["devices"] == {} and red["idle"] is None
    assert red["window_s"] > 0
