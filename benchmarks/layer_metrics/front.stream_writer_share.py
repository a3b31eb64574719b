"""Share of the window's token events that the front's stream writer handed
to their sockets, in percent: the difference of the lanes'
`stream.writer_events` over the difference of `writer_events +
handler_events` (`ContinuousGenerator.stats()["stream"]`). A writer event
went out in the one pass a scheduler tick that a front server's single
delivery thread makes over every stream whose source is an in-process
lane's outbox; a handler event went out on its request's own thread, as
every event did before PR 43: the stream was never given to a writer (a
journal or a caller iterates it), its socket would not take a whole frame
(`would_block`), it stalled, or the front was stopping
(`handler_by_reason`; `writer_passes` and `would_block` beside them). Near
100 says the mechanism engaged: a tick's events cost two hand-offs of the
interpreter lock, not one a stream. A program that does not count them
(before PR 43) reads nothing, as does a window without a token event.
Layer: HTTP front and gateway. Moves tokens_per_s."""


def compute(run):
    by_writer = by_handler = 0
    for node, after in run["stats_after"].items():
        stream = after.get("stream")
        before = run["stats_before"].get(node, {}).get("stream")
        if not stream or before is None or "writer_events" not in stream:
            continue
        by_writer += stream["writer_events"] - before["writer_events"]
        by_handler += stream["handler_events"] - before["handler_events"]
    events = by_writer + by_handler
    return 100.0 * by_writer / events if events else None
