"""blocks_per_dispatch.serve: blocks that rode in a dispatch shared by
several requests, per batcher dispatch (change of
``serve.coalesced_blocks`` over change of ``serve.dispatches``)."""


def read(ctx):
    dispatches = ctx["counters"].get("serve.dispatches", 0)
    if dispatches <= 0:
        return None
    return ctx["counters"].get("serve.coalesced_blocks", 0) / dispatches
