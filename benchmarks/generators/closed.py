"""Closed loop: ``clients`` callers, each sending its next request the
moment its last one completes — callers that wait for a reply. A slow
server therefore receives less load; the rate is an outcome.

Lengths: every seed draws the same multiset, round by round, in another
order (``harness/lengths.py``). Each client's FIRST request is cut to a drawn share of
its lengths (the shares evenly spaced over the clients), so that the
clients are out of phase from the first step and the window opens on a
steady state after seconds, not after a round of full requests. Those
first requests are warm-up and never samples.

``order`` (a key of the traffic file, a whole number; PR 38). In a
closed loop with no think time the lengths' order IS the arrivals: a
client's next request starts a fixed number of steps after its last, so
which prompts' chunks share a step, and with that how wide every step
is, follows from the order alone. Two seeds' windows then hold two
samples of some thousand steps and their percentiles read 2% and more
apart where two runs of one seed agree to 0.1% (PERF.md section 2).
With the key, the rounds and the first cuts are drawn from ``order``
and the seed seats the clients on them (which client holds which
sequence of lengths) and draws every token: every seed sends the same
requests at the same steps, from other clients, with other tokens.
Without it the seed draws the order too, as before PR 38.

Samples: the full requests that COMPLETE inside the window. A request's
due time is the moment its client saw the previous one complete.
"""
import numpy as np

from benchmarks.harness import lengths
from benchmarks.harness.loop import Sent

ROUNDS = 64  # request lengths drawn per client; a window uses far fewer


class Generator:
    def __init__(self, traffic, rng, vocab, seconds):
        self.warmup_s = float(traffic["warmup_s"])
        self.vocab = vocab
        n = int(traffic["clients"])
        # ``order`` (a whole number): the lengths and the first cuts are
        # drawn from IT and the seed only seats the clients on them
        # (below); absent, the seed draws them
        order = (np.random.default_rng(int(traffic["order"]))
                 if "order" in traffic else rng)
        # round by round the clients hold one length from each of n
        # strata, in a drawn order: whatever part of the rounds a
        # window sees, it sees the same work under every seed
        rounds = [list(zip(lengths.block(traffic["prompt_tokens"], n, r, order),
                           lengths.block(traffic["answer_tokens"], n, r, order)))
                  for r in range(ROUNDS)]
        shares = lengths.unit_block(n, 0, order)
        seat = rng.permutation(n) if order is not rng else range(n)
        self.queues = []
        for c in seat:
            queue = [rounds[r][c] for r in range(ROUNDS)]
            p, a = queue[0]
            queue[0] = (max(8, round(p * shares[c])), max(2, round(a * shares[c])))
            self.queues.append(queue)
        # each client draws its own tokens, in its own order, whatever
        # order the clients complete in
        self.rngs = rng.spawn(n)
        self.round = [0] * n
        self.ready = []

    def start(self, t0):
        self.ready = [(c, t0) for c in range(len(self.queues))]

    def due(self, now):
        out = []
        for c, since in self.ready:
            i = self.round[c]
            # past the drawn rounds, go round again without the cut first one
            prompt, answer = self.queues[c][i and 1 + (i - 1) % (ROUNDS - 1)]
            self.round[c] = i + 1
            out.append(Sent(lengths.tokens(self.rngs[c], prompt, self.vocab),
                            answer, due=since, judged=i > 0, client=c))
        self.ready = []
        return out

    def completed(self, sent, now):
        self.ready.append((sent.client, now))
