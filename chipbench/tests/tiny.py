"""A benchmark at a size a CPU test can run: the real harness, drivers,
generator, reference and metric readers, over a small configuration.

`make` copies this benchmark's ``metrics/`` into a fresh directory,
writes a small configuration and two small traffic mixes there (the
same kinds of file the real cells use) and returns a BENCHMARK.json-like
dict whose cells point at them.  `run` drives one cell through
`chipbench.run.run_cell` on whatever devices JAX has, as the benchmark
does on the chip, without the harness's look for a chip.
"""
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

CONFIG = {"name": "tiny", "taps": 255, "n_div": 100, "window": "hamming",
          "select": "even", "n_filters": 32, "coeff_bits": 16,
          "sample_bits": 8}
TRAFFIC = {
    "stream": {"loop": "closed", "engine": "single", "channels": 2,
               "chunk": 2048},
    "sharded": {"loop": "closed", "engine": "sharded", "channels": 1,
                "chunk": 2048},
    "tenants": {"loop": "open", "tenants": 8, "rows_per_tenant": 4,
                "lanes": 2, "journal_fsync": True, "chunk": 512,
                "rate_chunks_per_s": 16.0, "warm_depths": [1, 2]},
}
PEAK = {"bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 1e11,
        "hbm_bytes": 1e10}
SEED = 2 ** 31 + 7


def make(root: pathlib.Path, traffic=("stream", "tenants")) -> dict:
    shutil.copytree(BENCH / "metrics", root / "metrics")
    (root / "configs").mkdir()
    (root / "traffic").mkdir()
    with open(root / "configs" / "tiny.json", "w") as f:
        json.dump(CONFIG, f)
    for name in traffic:
        with open(root / "traffic" / f"{name}.json", "w") as f:
            json.dump(TRAFFIC[name], f)
    with open(BENCH.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "configs/tiny.json", "why": "test"}]
    bench["workloads"] = [{"name": f"tiny.{t}", "config": "tiny",
                           "traffic": t, "chips": 1, "why": "test"}
                          for t in traffic]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


def run(bench: dict, root: pathlib.Path, workload: str, seconds=0.6,
        trace=False, make_engine=None) -> dict:
    import jax

    from chipbench import run as harness

    out, _ = harness.run_cell(bench, workload, SEED, seconds, trace,
                              jax.devices(), PEAK, root=root, bench_dir=root,
                              started=lambda: 1.0, make_engine=make_engine)
    return out
