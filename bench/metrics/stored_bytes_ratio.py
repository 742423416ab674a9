"""stored_bytes_ratio: the plan's stored format bytes (a count the plan
reports) over the matrix's 4 bytes of fp32 value per nonzero."""


def read(run):
    stored = run.facts.get("stored_bytes")
    if not stored:
        return None
    return stored / (4 * run.facts["nnz"])
