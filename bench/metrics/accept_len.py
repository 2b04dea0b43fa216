"""Speculation: mean tokens committed per lane per verify round
(``ServingStats.accept_len``) over the rounds of the window."""


def read(ctx):
    s0, s1 = ctx["window"]
    n = s1["accept_len_n"] - s0["accept_len_n"]
    if n <= 0:
        return None
    return (s1["accept_len_sum"] - s0["accept_len_sum"]) / n
