"""``BENCHMARK.json`` against the benchmark's contract, and every name in it
against the file the harness finds it by."""
import importlib
import json
import pathlib
import re

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# keys that name a width, which a cut may never change
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|_dim$"
                   r"|_rank$|head|expan|experts_per_tok|n_inner|n_embd"
                   r"|d_model|d_ff)", re.IGNORECASE)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(MAN["run_seconds"], int)
    assert 1 <= MAN["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_command_and_paths():
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = MAN["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        assert not w.startswith("/") and ".." not in w
        if (ROOT / w).is_file():
            assert any(w.startswith(p + "/") for p in MAN["paths"]), w


def test_configs_resolve():
    assert 1 <= len(MAN["configs"]) <= 24
    used = {w["config"] for w in MAN["workloads"]}
    files = set()
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        assert sorted(c["reduced"]) == sorted(body["reduced"])
        for k in c["reduced"]:
            assert NAME.match(k) and k in body and not WIDTH.search(k), k


def test_workloads_resolve():
    wls = MAN["workloads"]
    assert 1 <= len(wls) <= 24
    assert len({w["name"] for w in wls}) == len(wls)
    assert len({(w["config"], w["traffic"]) for w in wls}) == len(wls)
    four = sum(w["chips"] == 4 for w in wls)
    assert four <= max(len(wls) // 2, 1)
    for w in wls:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (HERE / "mixes" / f"{w['traffic']}.json").is_file()
        assert (HERE / "limits" / f"{w['name']}.json").is_file()


def _metrics():
    return MAN["end_to_end"] + MAN["per_layer"]


def test_metric_entries():
    names = [m["name"] for m in _metrics()]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(MAN["end_to_end"]) <= 16
    assert 1 <= len(MAN["per_layer"]) <= 128
    wl = {w["name"] for w in MAN["workloads"]}
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", wl)) <= wl
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        mod = importlib.import_module(f"benchmarks.chip.metrics.{m['name']}")
        assert callable(mod.read)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert all(len(v) == 1 for v in layers.values())
    for m in _metrics():
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES


@pytest.mark.parametrize("w", [w["name"] for w in MAN["workloads"]])
def test_every_cell_reports_enough(w):
    e2e = [m["name"] for m in MAN["end_to_end"]
           if w in m.get("workloads", [w])]
    per = [m["name"] for m in MAN["per_layer"]
           if w in m.get("workloads", [w])]
    assert "setup_s" in e2e and len(e2e) >= 2 and per


def test_run_refuses_a_host_without_a_tpu(capsys):
    from benchmarks.chip import run as RUN
    w = MAN["workloads"][0]["name"]
    rc = RUN.main(["--workload", w, "--seed", "2147483659", "--seconds",
                   "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "not tpu" in out.err
