"""The layer-time readers (``metrics/_layer_time.py`` and the metrics on it)
in a traced rehearsal on the CPU: every new reader of one serve and one
train cell is loaded, ``program_scopes`` builds a table for every program
the run registered, and each reader returns None (a CPU trace has no device
line) without raising.  Their arithmetic on a hand-built trace is in
``tests/test_program_scopes.py``."""

import argparse

import pytest

from benchmark import run as harness

NEW = ("decode_step_ms.", "prefill_share.", "train_step_ms.",
       "device_time_unattributed_share.")


@pytest.mark.parametrize("cell,programs", [
    ("sc2-7b.serve-complete", {"generation.decode", "generation.prefill_16",
                               "generation.prefill_32"}),
    ("sc2-3b.train-4k", {"MultiLayerNetwork.train_step"})])
def test_traced_rehearsal_builds_the_tables_and_reads_nothing(cell, programs):
    manifest = harness.load_manifest()
    listed = [m["name"] for m in
              harness.metrics_of_cell(manifest, "per_layer", cell)
              if m["name"].startswith(NEW)]
    assert len(listed) >= 7, listed
    line = harness.run(argparse.Namespace(
        workload=cell, seed=2147483659, seconds=1.0, trace=1,
        rehearsal=True, describe=None))
    assert not set(listed) & set(line["values"])
    # the tables were built: one compile-and-parse a registered program
    assert programs <= set(line["notes"]["program_scopes_s"])
    assert line["would_be_correct"] is True, line["compared"]
