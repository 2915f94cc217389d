"""Generated tokens in the replies to every request due in the window,
over the time from the window's opening to the last of those replies:
all the work the window offered, over all the time it took. A request
with no reply adds no tokens and counts until the client gave up."""


def read(run):
    give_up = run.seconds + float(run.cell.mix["reply_cap_s"])
    done = [r["recv_s"] if r["recv_s"] is not None else give_up
            for r in run.requests]
    tokens = sum(len(r["tokens"] or []) for r in run.requests)
    return tokens / max(done)
