"""Share of the block table that the paged decode kernel walks: the
increase of the engine's ``serve.decode.kv_pages`` counter (live pages
read, summed over slots and steps) over that of ``serve.steps`` times the
table's entries (slots x pages a slot)."""


def read(w):
    c = w.get("counters") or {}
    if (not c.get("serve.steps") or "serve.decode.kv_pages" not in c
            or not w.get("block_table")):
        return None
    slots, pages = w["block_table"]
    return 100.0 * c["serve.decode.kv_pages"] / (c["serve.steps"] * slots
                                                 * pages)
