"""Host seconds of the program's own ingest in set-up (`bucketize` and
`normalize_rows`), timed by the benchmark around its calls."""


def read(rec):
    return rec["run"].get("host_build_s")
