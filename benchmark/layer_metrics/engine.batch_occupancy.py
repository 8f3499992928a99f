"""Tokens the decode blocks delivered over what they could have:
generated tokens that came out of decode blocks / (decode blocks x block_steps
x max_batch), in percent. The first token of each request comes from its
insert and is not a decode block's."""


def read(record):
    stats, eng = record.get("engine_stats"), record.get("engine")
    if not stats or not stats.get("decode_blocks"):
        return None
    from_blocks = stats["generated_tokens"] - stats["inserted_requests"]
    return 100.0 * from_blocks / (stats["decode_blocks"] * eng["block_steps"] * eng["max_batch"])
