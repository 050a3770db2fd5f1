"""Front end: seconds from the first line of the program's build to the
end of `exe.run(startup)`, by the benchmark's clock."""


def compute(run):
    spans = run.spans.get("build")
    return sum(end - start for start, end in spans) if spans else None
