"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
at a small size on the CPU, with one fault planted in the program: an
answer altered where it is produced, and a step that leaves its state
unchanged.  (The cell has no batch mean to halve and no exchange between
chips to drop.)  The sound run beside them is correct.
"""

from smallcells import run_small, small_cell

SEED = 2**32 + 99


def test_exec_sound_run_is_correct():
    assert run_small(small_cell("randwire274-exec"), SEED)["correct"]


def test_exec_answer_altered(monkeypatch):
    import repro.core.executor as ex

    real = ex.PlanProgram._program

    def altered(self, arena, ext):
        outs, arena = real(self, arena, ext)
        return (outs[0].at[3].add(0.01),) + tuple(outs[1:]), arena

    monkeypatch.setattr(ex.PlanProgram, "_program", altered)
    res = run_small(small_cell("randwire274-exec"), SEED)
    assert not res["correct"]
    assert res["checks"]["max_abs_err"]["value"] > 0.009


def test_exec_state_unchanged(monkeypatch):
    import repro.core.executor as ex

    real = ex.arena_write

    def stale(arena, x, offset, **kw):
        # every node but the graph's input leaves the arena as it was
        return real(arena, x, offset, **kw) if offset == 0 else arena

    monkeypatch.setattr(ex, "arena_write", stale)
    res = run_small(small_cell("randwire274-exec"), SEED)
    assert not res["correct"]
