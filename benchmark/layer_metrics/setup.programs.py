"""Programs the program built before the window opened (``CausalLM.compile_ms``
entries; for training, XLA compilations seen by the compile listener)."""


def read(record):
    return record.get("compile", {}).get("programs")
