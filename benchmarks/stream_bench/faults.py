"""Faults planted under the timed path, each of which a sound comparison
must catch: ``test_faults.py`` drives the harness with them on the CPU,
and this script reads one on the chip at a cell's own size::

    python3 benchmarks/stream_bench/faults.py state_unchanged \
        --workload so-table2.saturate --seed 12345 --seconds 10 --trace 0

prints the run's result line as ``run.py`` does; ``correct`` has to be
false. Each ``plant_*`` takes a ``setattr``-like function (pytest's
``monkeypatch.setattr`` in the tests, plain ``setattr`` here).

* ``state_unchanged``: the ingest dispatch returns its state unchanged
  (and no new results);
* ``half_batch``: half of each supervisor batch is left out;
* ``answer_altered``: one result pair per decode is reversed where the
  engine produces it.

The cells run on one chip, so there is no exchange between chips to
leave out."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def plant_state_unchanged(set_attr) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import executor

    orig = executor._ingest_frontier

    def stuck(arrays, *args, **kwargs):
        out = orig(jax.tree.map(jnp.copy, arrays), *args, **kwargs)
        return (arrays, jnp.zeros_like(out[1])) + tuple(out[2:])

    set_attr(executor, "_ingest_frontier", stuck)


def plant_half_batch(set_attr) -> None:
    from repro.streaming.service import PersistentQueryService

    orig = PersistentQueryService.ingest

    def half(self, stream, *args, **kwargs):
        events = list(stream)
        return orig(self, events[:len(events) // 2], *args, **kwargs)

    set_attr(PersistentQueryService, "ingest", half)


def plant_answer_altered(set_attr) -> None:
    from repro.core.engine import BatchedDenseRPQEngine

    orig = BatchedDenseRPQEngine._decode_new_into

    def altered(self, arr, vertex_of, t, fresh):
        before = [set(f) for f in fresh]
        orig(self, arr, vertex_of, t, fresh)
        for q, f in enumerate(fresh):
            for x, y in f - before[q]:
                if x != y:
                    f.discard((x, y))
                    f.add((y, x))
                    return

    set_attr(BatchedDenseRPQEngine, "_decode_new_into", altered)


FAULTS = {
    "state_unchanged": plant_state_unchanged,
    "half_batch": plant_half_batch,
    "answer_altered": plant_answer_altered,
}


def main(argv) -> int:
    if not argv or argv[0] not in FAULTS:
        print(f"usage: faults.py {{{','.join(FAULTS)}}} <run.py arguments>",
              file=sys.stderr)
        return 2
    import run

    FAULTS[argv[0]](setattr)
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
