"""How full the pending-reply buffer ever got (%): 100 x
``streamsim.reply_backlog_peak`` (each program's largest count of
replies waiting to be published or delivered) / ``streamsim.
reply_capacity`` (the buffer's rows) over the window.  A buffer that
never fills is padded work in every scan step."""

from chipbench import recorded

recorded.start()


def read(obs):
    peak = recorded.counter(obs, "streamsim.reply_backlog_peak")
    rows = recorded.counter(obs, "streamsim.reply_capacity")
    if peak is None or not rows:
        return None
    return 100.0 * peak / rows
