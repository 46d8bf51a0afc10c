"""The harness's contract off the chip."""
import json
from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]


def test_without_a_tpu_no_result_and_nonzero_exit(capsys):
    rc = run.main(["--workload", "archive.interactive", "--seed",
                   str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_names_only_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert callable(run.metric_reader(m["name"]))
        assert set(m["workloads"]) <= {w["name"] for w in spec["workloads"]}
