"""Peak bytes in use on the fullest device after the window
(``memory_stats()["peak_bytes_in_use"]``, read before the program's state is
freed and the reference runs): what decides how many rows a chip holds."""


def read(run):
    peak = run.state.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
