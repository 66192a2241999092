"""Reads of a stored working set through one client session.

Set-up stores ``stored_blobs`` blobs of ``blob_chunksets`` chunksets, made
from the seed, through ``ShelbyClient.put``; crashes the fewest SPs that
leave every stored chunkset with ``erased_data_chunks`` data chunks erased;
then reads every stored chunkset once, which compiles every decode shape
the window will see.  A request reads one range of one stored blob.  The
check compares every read of the window with the source bytes and settles
the session.
"""
from bench import check, traffic


class Op:
    span = "bench.read"

    def __init__(self, dep, mix, seed, log):
        from repro.storage.sdk import SettlementError

        self.dep, self.log, self.settlement_error = dep, log, SettlementError
        cs = dep.layout.chunkset_bytes
        self.sources = traffic.stored_blobs(mix, cs, seed)
        self.metas = [dep.client.put(data, payment=1.0, epochs=10) for data in self.sources]
        self.victims = dep.crash_to_erase(self.metas, mix["erased_data_chunks"])
        log(f"stored {len(self.metas)} blobs ({sum(map(len, self.sources))} bytes); crashed SPs "
            f"{self.victims}: every chunkset has at least {mix['erased_data_chunks']} data "
            f"chunks erased")
        with dep.client.session() as warm:
            for meta in self.metas:
                for c in range(meta.num_chunksets):
                    warm.read(meta.blob_id, c * cs, cs)
        self.session = dep.client.session()

    def issue(self, req):
        return self.session.read(self.metas[req.blob].blob_id, req.offset, req.length).data

    def check(self, checks, done) -> None:
        checks.add("wrong_reads", check.wrong_reads(done, self.sources), 0)
        checks.add("failed_reads", sum(d.error is not None for d in done), 0)
        try:
            self.session.close()
            violations = 0
        except self.settlement_error as e:
            self.log(f"settlement: {e}")
            violations = 1
        checks.add("settlement_violations", violations, 0)

    def chunksets(self, done, counters) -> int:
        """Chunksets the window decoded: what the per-chunkset metrics divide by."""
        return counters["chunksets_decoded"]

    def work(self, done) -> dict:
        """Chunksets the window's reads covered, and how many of them had data
        chunks on crashed SPs, so had to be decoded from parity."""
        cs, k, down = self.dep.layout.chunkset_bytes, self.dep.layout.k, set(self.victims)
        reads = erased = 0
        for d in done:
            r, meta = d.request, self.metas[d.request.blob]
            for c in range(r.offset // cs, -(-(r.offset + r.length) // cs)):
                reads += 1
                erased += any(meta.placement[(c, ck)] in down for ck in range(k))
        return {"chunkset_reads": reads, "chunkset_reads_with_erased_data": erased,
                "crashed_sps": len(self.victims)}
