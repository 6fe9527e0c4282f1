"""Share of the paged decode kernel's compute blocks that it ran, in %:
100 x `PagedStats.attn_blocks_live` over `attn_blocks_total`, over the
window. A block is a run of consecutive page-table entries of one slot
across all KV heads; the kernel copies and computes only the blocks that
hold a live row of an active slot, and the total is slots x blocks per
page-table row, per decode step."""


def read(rec):
    s = rec.counts.get("stats") or {}
    live, total = s.get("attn_blocks_live"), s.get("attn_blocks_total")
    return 100.0 * live / total if live is not None and total else None
