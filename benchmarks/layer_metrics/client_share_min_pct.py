"""Client / bridge: fairness as the tasks feel it — the completed queries
of the least-served client over an even share of all completed queries
(all ÷ clients), in percent.  100: every client got the same number of
answers; a starved client pulls it down.  From the harness's own samples
(client, t_sent, seconds): the clients' clock.  One client has nobody to
share with: nothing to read."""


def read(ctx):
    clients = len(ctx["loop"].clients)
    done = [0] * clients
    for client, _, dt in ctx["loop"].samples:
        if dt is not None:
            done[client] += 1
    if clients < 2 or not sum(done):
        return None
    return min(done) / (sum(done) / clients) * 100.0
