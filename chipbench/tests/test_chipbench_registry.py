"""A configuration, its data generator, a traffic mix, a driver and a
metric added as files, with an entry in BENCHMARK.json, are found by
name: no existing file of the benchmark changes."""
import json
import shutil
import subprocess
import sys
import textwrap

from chipbench.lib import harness


def test_new_files_are_found_by_name(tmp_path):
    root = harness.ROOT
    shutil.copytree(root / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in
              (tmp_path / "chipbench").rglob("*") if p.is_file()}
    cb = tmp_path / "chipbench"
    cfg = json.loads((cb / "configs" / "table3.json").read_text())
    cfg["name"] = "table3_x"
    cfg["data"].update(generator="gen_x", size_key="n_x", params={"n_x": 3})
    (cb / "generators" / "gen_x.py").write_text(
        "def generate(seed, n_x):\n    return [((seed,),)] * n_x\n")
    (cb / "configs" / "table3_x.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "burst_x.json").write_text(json.dumps(
        {"kind": "echo_x", "arrivals": "poisson", "rate_per_s": 5}))
    (cb / "kinds" / "echo_x.py").write_text(textwrap.dedent("""
        from chipbench.lib import gen
        def run(ctx):
            ctx.setup_done()
            db = gen.database(ctx.config, 14)
            return {"attempted": 1, "failed": 0,
                    "value_x": float(len(db) * db[0][0][0]),
                    "checks": {"ok": {"value": 0, "limit": 0}}}
        """))
    (cb / "metrics" / "new.metric_x.py").write_text(
        "def read(rec):\n    return rec.get('value_x')\n")
    bench["workloads"].append({"name": "table3_x.burst", "config":
                               "table3_x", "traffic": "burst_x",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new.metric_x", "unit": "x",
                               "better": "lower", "source": "host_clock",
                               "layer": "test", "moves": "setup_s",
                               "workloads": ["table3_x.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = textwrap.dedent("""
        import argparse, json, sys, time
        sys.path.insert(0, '.')
        from chipbench.lib import harness
        spec = harness.cell('table3_x.burst')
        assert spec['config']['name'] == 'table3_x'
        assert spec['mix']['kind'] == 'echo_x'
        args = argparse.Namespace(workload='table3_x.burst', seed=1,
                                  seconds=1, trace=1, control=None)
        print(json.dumps(harness.result_line(
            harness.load_module('kinds', 'echo_x').run(
                harness.Context(args, spec, time.perf_counter())),
            spec, True, {})))
        """)
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["metrics"] == {"new.metric_x": {"value": 42.0, "unit": "x"}}
    for path, data in before.items():
        assert path.read_bytes() == data, path
