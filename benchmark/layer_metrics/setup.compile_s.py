"""Seconds the program spent lowering and compiling its own programs
(``CausalLM.compile_ms`` for serving, the first step's wall for training);
on persistent-cache hits this is the time to trace, lower and load them."""


def read(record):
    return record.get("compile", {}).get("compile_s")
