"""dec_ct_per_s: decryptions whose decoded slots reached host memory
inside the window, over the window's seconds (host clock)."""


def read(r):
    done = [d for d in r.window.requests if d.kind == "dec"
            and d.t_done is not None and d.t_done < r.window.t1]
    return len(done) / r.seconds
