"""What the offload gate decided and what the surrogate served, with the
conditioning set each used, so that the checks can rebuild both with the
plain reference once the window has closed.

`GateLog(offload, served)` wraps two methods of one `SurrogateOffload`
instance: `trust_sd`, the gate's trust launch at push time, and
`evaluate`, the surrogate's answer to an offloaded task.  Each call is
logged with the input and the engine that answered it.  The first time an
engine is seen, its conditioning set (inputs and outputs, float32) is
copied to the host: once per conditioning, never per task."""
from __future__ import annotations

import threading
import weakref
from typing import List, Optional, Tuple

import numpy as np

from bench.harness.sampling import input_key


class GateLog:
    def __init__(self, offload, served):
        self.sets: List[Tuple[np.ndarray, np.ndarray]] = []
        # (input key, the program's latent sd, set index or None)
        self.trust: List[Tuple[bytes, float, Optional[int]]] = []
        # (input key, set index or None)
        self.answers: List[Tuple[bytes, Optional[int]]] = []
        self._index = weakref.WeakKeyDictionary()
        self._lock = threading.Lock()
        trust_sd, evaluate = offload.trust_sd, offload.evaluate

        def logged_trust_sd(thetas):
            eng = offload._engine
            sd = trust_sd(thetas)
            same = offload._engine is eng
            for theta, v in zip(thetas, np.asarray(sd)):
                self.trust.append((input_key(theta), float(v),
                                   self._set_of(eng) if same else None))
            return sd

        def logged_evaluate(parameters):
            eng = offload._engine
            out = evaluate(parameters)
            same = offload._engine is eng
            served(parameters)
            self.answers.append((input_key(parameters),
                                 self._set_of(eng) if same else None))
            return out

        offload.trust_sd = logged_trust_sd
        offload.evaluate = logged_evaluate

    def _set_of(self, eng) -> int:
        """Index of the engine's conditioning set in `sets`.  An engine
        that read the same before and after a call is the one that
        answered it: engines are replaced, never restored."""
        with self._lock:
            i = self._index.get(eng)
            if i is None:
                i = len(self.sets)
                self.sets.append((np.asarray(eng.x, np.float32),
                                  np.asarray(eng.y, np.float32)))
                self._index[eng] = i
            return i
