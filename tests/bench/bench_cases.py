"""The 2x2 HPL cell that BENCHMARK.json does not hold yet: its mix and the
collective-exposure reader are in bench/, and the tests run its path."""

TWO_BY_TWO = {"name": "hpl.n32768.2x2", "config": "hpl-ai-fp32",
              "traffic": "n32768.grid2", "chips": 4,
              "why": "weak scaling on a 2x2 torus"}


def spec_with_2x2() -> dict:
    from bench.harness import load_benchmark
    spec = load_benchmark()
    return dict(spec, workloads=spec["workloads"] + [TWO_BY_TWO])
