"""Puts of fresh blobs through ``ShelbyClient.put``.

Set-up puts one blob, which compiles the DAS extension.  A request puts the
fresh blob ``traffic.put_blob`` makes from the seed.  The check, after the
window: no put failed; every chunk of every acknowledged put is on its SP,
each data chunk equal to its part of the blob; and every acknowledged put
is read back, chunkset by chunkset, with SPs holding data chunks crashed,
as many as the configuration guarantees a put survives.  With ``m`` of them
down, the read has exactly ``k`` chunks left and decodes from every parity
chunk, so a wrong parity byte makes it wrong.
"""
from bench import check, traffic


class Op:
    span = "bench.put"

    def __init__(self, dep, mix, seed, log):
        self.dep, self.mix, self.seed, self.log = dep, mix, seed, log
        dep.client.put(traffic.rng_for(seed, 4).bytes(mix["put_bytes"]), payment=1.0, epochs=10)
        self.read_back = 0

    def issue(self, req):
        return self.dep.client.put(traffic.put_blob(self.mix, self.seed, req.blob),
                                   payment=1.0, epochs=10)

    def check(self, checks, done) -> None:
        dep = self.dep
        acked = [(d.answer, traffic.put_blob(self.mix, self.seed, d.request.blob))
                 for d in done if d.error is None]
        checks.add("failed_puts", sum(d.error is not None for d in done), 0)
        checks.add("wrong_stored_chunks",
                   check.wrong_stored_chunks(acked, dep.sps, dep.config), 0)
        survive = dep.config["guarantees"]["acked_puts_survive_sp_failures"]
        cs = dep.layout.chunkset_bytes
        wrong = 0
        with dep.client.session() as session:
            for i, (meta, source) in enumerate(acked):
                for c in range(meta.num_chunksets):
                    victims = dep.crash_data_holders(meta, survive, c)
                    lo, hi = c * cs, min((c + 1) * cs, len(source))
                    try:
                        wrong += session.read(meta.blob_id, lo, hi - lo).data != source[lo:hi]
                    except Exception as e:  # an acknowledged put that cannot be read is wrong
                        self.log(f"read-back of put {i} chunkset {c} failed: "
                                 f"{type(e).__name__}: {e}")
                        wrong += 1
                    for sp_id in victims:
                        dep.sps[sp_id].recover()
                    self.read_back += 1
        self.log(f"read back {self.read_back} chunksets of {len(acked)} acknowledged puts, "
                 f"{survive} SPs with data chunks down for each")
        checks.add("wrong_readbacks", wrong, 0)

    def chunksets(self, done, counters) -> int:
        """Chunksets the window's acknowledged puts encoded."""
        cs = self.dep.layout.chunkset_bytes
        return sum(-(-d.request.length // cs) for d in done if d.error is None)

    def work(self, done) -> dict:
        return {"puts_acked": sum(d.error is None for d in done),
                "chunksets_read_back": self.read_back}
