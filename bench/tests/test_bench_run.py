"""Whole runs of each cell on the CPU at a tiny scale, with the look for
a chip skipped: a sound run is ``correct``, and each fault the cell can
have, planted in the timed path, makes ``correct`` false."""
import dataclasses

import numpy as np
import pytest

import harness
import system
from reference import queries

P = system.load_program()


def _add_one_to_every_sum(monkeypatch):
    fin = P.database.PimDatabase._finalize_aggs

    def altered(group_regs, read_scalar, read_reduce):
        out = fin(group_regs, read_scalar, read_reduce)
        return {g: {k: v + 1 if isinstance(v, (int, np.integer)) else v
                    for k, v in aggs.items()} for g, aggs in out.items()}

    monkeypatch.setattr(P.database.PimDatabase, "_finalize_aggs",
                        staticmethod(altered))


def _leave_out_half_the_rows(monkeypatch):
    run = P.program.run_program

    def half(cp, rel):
        n = rel.valid.shape[0]
        return run(cp, dataclasses.replace(rel,
                                           valid=rel.valid.at[n // 2:].set(0)))

    monkeypatch.setattr(P.program, "run_program", half)


def _alter_host_rows(monkeypatch):
    stage = P.exec.run_host_stage

    def altered(host, ctx):
        t = stage(host, ctx)
        col = next(iter(t.columns))
        if t.n_rows:
            t.columns[col] = t.columns[col] + 1
        return t

    monkeypatch.setattr(P.exec, "run_host_stage", altered)


def _refresh_leaves_state_unchanged(monkeypatch):
    apply = P.database.PimDatabase.apply
    calls = {"n": 0}

    def unchanged(self, mutations):
        calls["n"] += 1
        if calls["n"] <= 2:            # set-up's RF1 and RF2 go through
            return apply(self, mutations)
        return {P.dml.mutation_relation(m): {
            "n_mutations": 1, "n_rows": 1, "n_instructions": 1, "cycles": 1,
            "cells_written": 1, "version": 0} for m in mutations}

    monkeypatch.setattr(P.database.PimDatabase, "apply", unchanged)


def _chip_write_dropped(monkeypatch):
    """Each insert reaches the host's copy and the program's log, but
    none of its plane writes reaches the chip."""
    run = P.dml.RelationDml._run
    inserts = {"n": 0}

    def dropped(self, op, n_rows, instrs):
        if op == "insert":
            inserts["n"] += 1
            if inserts["n"] > 2:       # set-up's RF1 goes through
                run(self, op, n_rows, [i for i in instrs if not isinstance(
                    i, P.isa.PlaneWrite)])
                self.programs[-1] = (op, tuple(instrs))
                return
        run(self, op, n_rows, instrs)

    monkeypatch.setattr(P.dml.RelationDml, "_run", dropped)


FAULTS = {
    "sf1-power-array": {"answer_altered": _add_one_to_every_sum,
                        "half_the_rows": _leave_out_half_the_rows},
    "sf1-throughput-rf": {"answer_altered": _alter_host_rows,
                          "half_the_rows": _leave_out_half_the_rows,
                          "state_unchanged": _refresh_leaves_state_unchanged,
                          "chip_write_dropped": _chip_write_dropped},
}


@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_sound_run_is_correct(cpu_run, cell):
    out = cpu_run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    spec = harness.load_cell(cell)
    cell_metrics = {m["name"] for m in spec["end_to_end"]}
    assert set(out["metrics"]) == cell_metrics
    assert {"setup_s"} < cell_metrics
    # Whole passes only, and in the throughput cell one RF1/RF2 pair per
    # pass: every seed's window holds the same mix.
    mix = len(queries.names(spec["workload"]["params"]["queries"]))
    refreshes = 2 if spec["workload"]["traffic_kind"] == "throughput" else 0
    assert out["attempted"] % (mix + refreshes) == 0
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(FAULTS)
                                        for f in sorted(FAULTS[c])])
def test_fault_in_timed_path_is_not_correct(cpu_run, monkeypatch, cell,
                                            fault):
    FAULTS[cell][fault](monkeypatch)
    out = cpu_run(cell)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
