"""Ways to break the timed path underneath a run, for the control.

The system runs no model and states no precision, so the control breaks
a guarantee the configuration states ("every search answer equals the
plain reference"): an answer altered where it is produced, and a partial
answer (a block silently left out). `correct` must come out false.
Used by test_harness_data.py at the rehearsal's size and by control.py
at the cell's own size on the chip. Each is a `hook(stage, state)` for
`chipbench.run.run` and leaves `state["undo"]`.
"""


def drop_a_trace(stage, state):
    """From the end of set-up on, the HTTP layer loses the last trace of
    every search answer (the set-up's own answers stay sound)."""
    if stage != "warm":
        return
    from tempo_tpu.api import http

    orig = http.HTTPApi._route

    def broken(self, method, path, query, headers):
        code, resp = orig(self, method, path, query, headers)
        if path == "/api/search" and isinstance(resp, dict) \
                and resp.get("traces"):
            resp = dict(resp, traces=resp["traces"][:-1])
        return code, resp

    http.HTTPApi._route = broken
    state["undo"] = lambda: setattr(http.HTTPApi, "_route", orig)


def skip_a_block(stage, state):
    """From before the first request on, the header prune drops one
    block whatever the request says: its entries are neither inspected
    nor returned. A partial answer where the guarantee is an exact one."""
    if stage != "server":
        return
    from tempo_tpu.search import batcher

    orig = batcher.block_header_skip_reason
    victim = int(state["manifest"]["start"][0].min())
    batcher.block_header_skip_reason = lambda header, req: (
        "time_range" if header.get("min_start_s") == victim
        else orig(header, req))
    state["undo"] = lambda: setattr(batcher, "block_header_skip_reason", orig)


BREAKAGES = {"answer-altered": drop_a_trace, "block-skipped": skip_a_block}
