"""The comparison that decides ``correct``.

After the window has closed and the service is freed, the plain
reference (``reference.py``) recomputes a sample of the window's own
results, drawn from the seed and paired with them by request id:

- ``enc_c1_mismatch``: coefficients of INTT(c1 - v*a) that are not the
  encryption's e1, over the sampled encryptions and every limb. c1 is
  exact integer arithmetic, so the limit is 0. A wrong nonce, a wrong
  row of the batch, a dropped or swapped limb reads in the thousands.
- ``enc_c0_gap``: the largest |INTT(c0 - v*b) - e0 - round(Delta*m)|,
  centered mod each prime, in units of 1/Delta: how far the served
  encoding lies from the reference's float64 encoding of the same
  message. Its limit is the configuration's.
- ``dec_slot_gap``: the largest |served slot - reference slot| over the
  sampled decryptions. Its limit is the configuration's.
- ``missing``: requests sent in the window whose result never came
  (failed, or still missing a minute after the close). Limit 0.
- ``unchecked``: sampled results the check expected and did not get.
  Limit 0.
- ``window_compiles``: programs traced or compiled inside the window,
  and jit-cache entries it added. Limit 0.

Each number is printed beside its limit; a run is correct when every
number is at or under its limit.
"""

from __future__ import annotations

import numpy as np

from reference import Reference


def compare(config: dict, mix: dict, window, messages,
            ciphertexts) -> dict:
    """{name: (value, limit)} for one run. ``messages`` and
    ``ciphertexts`` are the run's pools, ``window`` a ``driver.Window``
    (or what ``control.py`` puts in the program's place)."""
    limits = config["check_limits"]
    checks = {
        "missing": (sum(d.t_done is None for d in window.requests), 0),
        "window_compiles": (window.compiles + window.jit_entries_added, 0),
    }
    wanted = {k: int(v) for k, v in mix.get("check", {}).items() if v}
    got = {k: len(window.samples.get(k, ())) for k in wanted}
    checks["unchecked"] = (sum(max(wanted[k] - got[k], 0)
                               for k in wanted), 0)
    ref = Reference(config)
    if wanted.get("enc"):
        mismatch, gap = 0, 0
        for done, (c0, c1) in window.samples["enc"]:
            nonce = window.nonces.get(done.rid)
            if nonce is None:
                mismatch += c1.size
                continue
            z = messages[done.index % len(messages)]
            g = ref.encrypt_gaps(z, nonce, c0, c1)
            mismatch += g["c1_mismatch"]
            gap = max(gap, g["c0_gap"])
        checks["enc_c1_mismatch"] = (mismatch, 0)
        checks["enc_c0_gap"] = (gap, limits["enc_c0_gap"])
    if wanted.get("dec"):
        gap = 0.0
        for done, slots in window.samples["dec"]:
            c0, c1, _scale = ciphertexts[done.index % len(ciphertexts)]
            want = ref.decrypt(c0, c1)
            gap = max(gap, float(np.max(np.abs(np.asarray(slots) - want))))
        checks["dec_slot_gap"] = (gap, limits["dec_slot_gap"])
    return checks


def passed(checks: dict) -> bool:
    return all(value <= limit for value, limit in checks.values())
