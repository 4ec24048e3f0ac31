"""``bench/run.py`` with a fault planted in the served path, for the tests.

    python bench/tests/faulty_run.py <fault> <run.py arguments...>

Faults, each planted where the program produces or routes an answer:

* ``none``: nothing planted;
* ``answer``: the executor's first output of every flush is altered by
  one part in a thousand;
* ``half``: the executor leaves out the second half of each flush's
  graphs (their outputs come back as zeros);
* ``route``: the scheduler hands each flush's outputs to its requests one
  place out of turn.
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as R  # noqa: E402


def plant(fault: str) -> None:
    from repro.serve import executor as X
    from repro.serve import scheduler as S

    run_flush = X.Executor.run
    if fault == "answer":
        def altered(self, p, model=None):
            out, dt = run_flush(self, p, model)
            out = out.copy()
            out[0] = out[0] * 1.001
            return out, dt
        X.Executor.run = altered
    elif fault == "half":
        def halved(self, p, model=None):
            out, dt = run_flush(self, p, model)
            out = out.copy()
            n = int(p.graph.n_graph)
            out[n // 2:n] = 0.0
            return out, dt
        X.Executor.run = halved
    elif fault == "route":
        unpack = S.unpack_outputs

        def shifted(out, meta, level="graph"):
            outs = unpack(out, meta, level=level)
            return outs[1:] + outs[:1]
        S.unpack_outputs = shifted
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    build = R.build_server

    def planted(*args, **kwargs):
        plant(fault)
        return build(*args, **kwargs)

    R.build_server = planted
    return R.main(argv)


if __name__ == "__main__":
    sys.exit(main())
