"""Child of ``test_perfbench_host_metrics.py``: one CPU rehearsal of a cell
through ``perfbench/run.py``'s own ``main``, with what its readers were
given (the traced train's span names and its counters) written as JSON to
the file named first; the other arguments are ``run.py``'s."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    dump, argv = sys.argv[1], sys.argv[2:]
    os.environ["JAX_ENABLE_X64"] = "0"     # as run.main does, before jax
    os.environ["TMOG_COST_HISTORY"] = ""
    sys.path.insert(0, ROOT)
    from perfbench import run
    from transmogrifai_tpu.utils import profiling

    # a tiny shape makes no array of 32 MiB: book every one
    profiling.FRESH_MIN_BYTES = 0
    read_metrics = run.read_metrics

    def spy(names, sources, problems):
        with open(dump, "w") as f:
            json.dump({"spans": sorted({s["name"] for s in
                                        sources["trace"]["spans"]}),
                       "counters": sources["counters"]}, f)
        return read_metrics(names, sources, problems)

    run.read_metrics = spy
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
