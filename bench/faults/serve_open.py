"""Faults planted in the timed path of a ``serve_open`` cell: the requests
go through the same engine as a path, so its two faults hold here too, and
the server can leave half of a batch out."""

from bench.faults.path import answer_altered, solver_state_unchanged


def half_batch_left_out(monkeypatch, cell):
    """``GlassoServer.solve_batch`` serves the first half of each batch and
    drops the rest; with no warm-up and a short settle the dropped requests
    never resolve."""
    from repro.launch.serve_glasso import GlassoServer

    real = GlassoServer.solve_batch

    def half(self, requests):
        return real(self, requests[: (len(requests) + 1) // 2])

    monkeypatch.setattr(GlassoServer, "solve_batch", half)
    cell.workload = dict(cell.workload, settle_seconds=3, warmup_seconds=0.0)
    return "unresolved"


FAULTS = {
    "solver_state_unchanged": solver_state_unchanged,
    "answer_altered": answer_altered,
    "half_batch_left_out": half_batch_left_out,
}
