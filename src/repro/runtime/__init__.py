from repro.runtime.steps import (
    StepBundle, bundle_for, decode_bundle, init_params, init_train_state,
    prefill_bundle, train_bundle,
)

__all__ = ["StepBundle", "bundle_for", "decode_bundle", "init_params",
           "init_train_state", "prefill_bundle", "train_bundle"]
