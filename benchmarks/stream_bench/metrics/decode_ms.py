"""Mean host time of the engine's decode of one dispatch's ``new`` mask
(``_decode_new_into``: nonzero scan and the per-pair loop; the
device->host transfer happens before it and is not included)."""


def read(rec):
    spans = rec["spans"].get("decode")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3
