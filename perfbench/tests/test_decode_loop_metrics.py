"""The eight per-layer metrics that read the decode loop's own account of
its time (PR 37): four quantities, one entry each for the LongCat cell
(``.lc``) and the Kimi cell (``.k2``).  All are data for the
``metrics_delta`` reader; each reads the right ratio from two /metrics
pages as the program renders them (labelled series summed by
``procs.parse_metrics``), and nothing from a program without the series."""

import pytest

from perfbench import manifest as M
from perfbench import procs
from perfbench import run as R
from perfbench.tests.test_longcat import drive, toy  # noqa: F401 - the fixture

QUANTITIES = ("decode_dry_pct", "decode_host_ms", "decode_offcpu_pct", "gc_pause_pct")
CELLS = {"lc": "longcat-agent-decode-closed128", "k2": "kimi-code-longprompt-closed64"}
PHASES = ("wait", "admit", "dispatch", "flush", "read", "book")


def _page(loop: dict, cpu: float, dry: dict, gc: tuple, steps: float, chunks: float) -> str:
    """A /metrics page's lines for these series, labelled as the program labels them."""
    m = 'model="lane"'
    lines = [f"kdlt_decode_loop_{p}_seconds_total{{{m}}} {loop[p]}" for p in PHASES]
    lines.append(f"kdlt_decode_loop_cpu_seconds_total{{{m}}} {cpu}")
    lines += [f'kdlt_decode_dry_seconds_total{{{m},phase="{p}"}} {v}' for p, v in dry.items()]
    lines += [f'kdlt_decode_dry_total{{{m},phase="{p}"}} 3' for p in dry]
    lines += [f'kdlt_gc_pause_seconds_total{{generation="{g}"}} {v}' for g, v in enumerate(gc)]
    lines += [f"kdlt_decode_steps_total{{{m}}} {steps}",
              f"kdlt_decode_prefill_chunks_total{{{m}}} {chunks}",
              "# HELP kdlt_xla_compile_requests_total x", "kdlt_xla_compile_requests_total 7"]
    return "\n".join(lines) + "\n"


BEFORE = _page(dict.fromkeys(PHASES, 1.0), 2.0,
               {"admit": 0.0, "flush": 0.0, "read": 0.0, "book": 0.0}, (0.1, 0.0, 0.0), 10, 2)
# 40 s of loop: 1 waiting, 36 reading, 3 of host phases (0.6 admit, 0.9 dispatch,
# 0.3 flush, 1.2 book) of which 2.4 on a CPU; 0.2 s dry (0.05 in flush, 0.15 in
# book); 0.3 s of GC pauses over the generations; 2,000 steps and 400 chunks read.
AFTER = _page({"wait": 2.0, "admit": 1.6, "dispatch": 1.9, "flush": 1.3, "read": 37.0,
               "book": 2.2}, 4.4,
              {"admit": 0.0, "flush": 0.05, "read": 0.0, "book": 0.15},
              (0.2, 0.1, 0.1), 2010, 402)
WANT = {"decode_dry_pct": 100 * 0.2 / 40, "decode_host_ms": 1000 * 3.0 / 2400,
        "decode_offcpu_pct": 100 * (3.0 - 2.4) / 3.0, "gc_pause_pct": 100 * 0.3 / 40}


def _specs(suffix):
    m = M.Manifest()
    return m, {e["name"].rsplit(".", 1)[0]: s
               for e, s in m.cell(CELLS[suffix]).per_layer
               if e["name"].rsplit(".", 1)[0] in QUANTITIES}


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("quantity", QUANTITIES)
def test_entry_resolves_its_data_file_and_reader(quantity, suffix):
    m = M.Manifest()
    m.validate()
    name = f"{quantity}.{suffix}"
    entry = [e for e in m.data["per_layer"] if e["name"] == name]
    assert entry == [{"name": name, "unit": "ms" if quantity.endswith("_ms") else "%",
                      "better": "lower", "source": "program_counter", "layer": "dispatch",
                      "moves": "latency_p50_ms", "workloads": [CELLS[suffix]]}]
    spec = _specs(suffix)[1][quantity]
    assert spec["reader"] == "metrics_delta" and spec["what"]
    assert hasattr(R.load_reader(m.bench_dir, "metrics_delta"), "read")
    other = CELLS["k2" if suffix == "lc" else "lc"]
    assert name not in {e["name"] for e, _ in m.cell(other).per_layer}


def test_the_eight_are_appended_at_the_end():
    names = [e["name"] for e in M.Manifest().data["per_layer"]]
    assert sorted(names[-8:]) == sorted(f"{q}.{s}" for q in QUANTITIES for s in CELLS)


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_each_reads_its_ratio_from_two_pages(suffix):
    m, specs = _specs(suffix)
    reader = R.load_reader(m.bench_dir, "metrics_delta")
    run = {"before": {"server": procs.parse_metrics(BEFORE)},
           "after": {"server": procs.parse_metrics(AFTER)}}
    for quantity, want in WANT.items():
        assert reader.read(specs[quantity], run) == pytest.approx(want, rel=1e-9), quantity


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_a_program_without_the_series_reads_nothing(suffix):
    """The parent's pages: none of the four is there to read."""
    m, specs = _specs(suffix)
    reader = R.load_reader(m.bench_dir, "metrics_delta")
    old = "kdlt_decode_steps_total 5\nkdlt_decode_prefill_chunks_total 1\n"
    new = "kdlt_decode_steps_total 9\nkdlt_decode_prefill_chunks_total 2\n"
    run = {"before": {"server": procs.parse_metrics(old)},
           "after": {"server": procs.parse_metrics(new)}}
    for quantity in QUANTITIES:
        assert reader.read(specs[quantity], run) is None, quantity


def test_a_rehearsed_run_carries_the_four_in_its_line(toy):  # noqa: F811 - the fixture
    """The LongCat cell's four, through the program's own server at toy
    widths on the CPU (``lctoy``: the real cell's metric files)."""
    _run, line = drive(toy, 2**31 + 37, trace=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {f"{q}.lc" for q in QUANTITIES} <= set(got)
    assert 0 <= got["decode_dry_pct.lc"] < 100 and got["decode_host_ms.lc"] > 0
    assert 0 <= got["decode_offcpu_pct.lc"] < 100 and 0 <= got["gc_pause_pct.lc"] < 100
