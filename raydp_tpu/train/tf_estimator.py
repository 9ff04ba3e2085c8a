"""TFEstimator-compatible trainer: the keras migration path (C13).

The reference's TFEstimator serializes keras objects to JSON strings and
rebuilds them on workers (reference: python/raydp/tf/estimator.py:87-132
— model ``to_json()``, optimizer/loss by name or serialized config,
``TFTrainer`` underneath). This module accepts the SAME wire formats — a
``model.to_json()`` string / parsed dict / plain Sequential layer-config
list, keras optimizer and loss identifiers — and lowers them onto the
TPU-native stack: an equivalent flax module trained by JAXEstimator
(SURVEY §7.1 maps TFEstimator → JAXEstimator). TensorFlow itself is
never imported.

Activation/loss fusion: keras models typically end in sigmoid/softmax
with a from-probabilities loss; this trainer strips that terminal
activation and uses the fused from-logits loss instead (same math,
numerically stabler, and the MXU-friendly form).
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from raydp_tpu.models.dropout import Dropout
from raydp_tpu.train.estimator import JAXEstimator, TrainingCallback

_ACTIVATIONS: Dict[str, Callable] = {
    "linear": lambda x: x,
    "relu": nn.relu,
    "tanh": jnp.tanh,
    "sigmoid": nn.sigmoid,
    "softmax": nn.softmax,
    "elu": nn.elu,
    "gelu": nn.gelu,
    "selu": nn.selu,
    "softplus": nn.softplus,
    "leaky_relu": nn.leaky_relu,
}

# keras loss identifier → (raydp loss name, terminal activation it fuses)
_LOSSES: Dict[str, Tuple[str, Optional[str]]] = {
    "mse": ("mse", None),
    "mean_squared_error": ("mse", None),
    "mae": ("mae", None),
    "mean_absolute_error": ("mae", None),
    "huber": ("huber", None),
    "huber_loss": ("huber", None),
    "binary_crossentropy": ("bce", "sigmoid"),
    "categorical_crossentropy": ("softmax_ce", "softmax"),
    "sparse_categorical_crossentropy": ("softmax_ce", "softmax"),
}

_METRICS = {
    "accuracy": "accuracy",
    "acc": "accuracy",
    "binary_accuracy": "binary_accuracy",
    "categorical_accuracy": "categorical_accuracy",
    "sparse_categorical_accuracy": "categorical_accuracy",
    "mse": "mse",
    "mae": "mae",
}


class KerasSequential(nn.Module):
    """Flax twin of a keras Sequential built from layer configs."""

    layer_configs: Tuple[Dict[str, Any], ...]

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        for cfg in self.layer_configs:
            cls = cfg["class_name"]
            c = cfg.get("config", {})
            if cls in ("InputLayer", "Input"):
                continue
            if cls == "Flatten":
                x = x.reshape((x.shape[0], -1))
            elif cls == "Dense":
                x = nn.Dense(int(c["units"]), name=c.get("name"))(x)
                act = c.get("activation", "linear") or "linear"
                x = _activation(act)(x)
            elif cls == "Dropout":
                x = Dropout(float(c.get("rate", 0.5)))(x, deterministic)
            elif cls == "Activation":
                x = _activation(c["activation"])(x)
            elif cls in ("BatchNormalization", "LayerNormalization"):
                # Inference-style normalization (no running stats across
                # the functional boundary) — LayerNorm is the drop-in.
                x = nn.LayerNorm(name=c.get("name"))(x)
            else:
                raise ValueError(
                    f"unsupported keras layer {cls!r}; supported: Dense, "
                    "Dropout, Activation, Flatten, InputLayer, "
                    "BatchNormalization/LayerNormalization"
                )
        return x


def _activation(name: str) -> Callable:
    fn = _ACTIVATIONS.get(name)
    if fn is None:
        raise ValueError(
            f"unsupported activation {name!r}; known: {sorted(_ACTIVATIONS)}"
        )
    return fn


def parse_keras_model(spec: Union[str, dict, list]) -> List[Dict[str, Any]]:
    """``model.to_json()`` string / parsed dict / plain layer-config list
    → normalized layer configs."""
    if isinstance(spec, str):
        spec = json.loads(spec)
    if isinstance(spec, dict):
        if spec.get("class_name") not in ("Sequential", "Functional"):
            raise ValueError(
                "only Sequential-style keras models are supported; got "
                f"{spec.get('class_name')!r}"
            )
        layers = spec.get("config", {}).get("layers", [])
    else:
        layers = list(spec)
    out = []
    for layer in layers:
        if not isinstance(layer, dict) or "class_name" not in layer:
            raise ValueError(f"malformed layer config: {layer!r}")
        out.append(layer)
    return out


def parse_keras_optimizer(spec: Union[str, dict, None]):
    """keras optimizer name or serialized config → optax transform."""
    if spec is None:
        return optax.adam(1e-3)
    if isinstance(spec, dict):
        name = spec.get("class_name", "").lower()
        cfg = spec.get("config", {})
    else:
        name, cfg = str(spec).lower(), {}
    lr = float(cfg.get("learning_rate", cfg.get("lr", 1e-3)))
    if name in ("adam",):
        return optax.adam(lr, b1=float(cfg.get("beta_1", 0.9)),
                          b2=float(cfg.get("beta_2", 0.999)))
    if name in ("adamw",):
        return optax.adamw(lr, weight_decay=float(
            cfg.get("weight_decay", 1e-4)
        ))
    if name in ("sgd",):
        momentum = float(cfg.get("momentum", 0.0)) or None
        return optax.sgd(lr, momentum=momentum)
    if name in ("rmsprop",):
        return optax.rmsprop(lr, decay=float(cfg.get("rho", 0.9)))
    if name in ("adagrad",):
        return optax.adagrad(lr)
    raise ValueError(f"unsupported keras optimizer {spec!r}")


class TFEstimator:
    """Drop-in for the reference TFEstimator's configuration surface
    (reference: tf/estimator.py:40-132): keras-format model/optimizer/
    loss/metrics in, scikit-style fit/evaluate/get_model/save/restore/
    shutdown out — running on JAX."""

    def __init__(
        self,
        num_workers: int = 1,
        model: Union[str, dict, list, None] = None,
        optimizer: Union[str, dict, None] = None,
        loss: str = "mse",
        metrics: Sequence[str] = (),
        feature_columns: Optional[List[str]] = None,
        label_column: Optional[str] = None,
        batch_size: int = 128,
        num_epochs: int = 1,
        shuffle: bool = True,
        callbacks: Sequence[TrainingCallback] = (),
        seed: int = 0,
        **extra,
    ):
        if model is None:
            raise ValueError("model (keras JSON/config) is required")
        layers = parse_keras_model(model)
        loss_name, fused_activation = _LOSSES.get(
            str(loss).lower(), (None, None)
        )
        if loss_name is None:
            raise ValueError(
                f"unsupported keras loss {loss!r}; known: {sorted(_LOSSES)}"
            )
        # Fuse the terminal probability activation into the loss.
        if fused_activation and layers:
            last = layers[-1]
            lc = last.get("config", {})
            if (
                last["class_name"] == "Activation"
                and lc.get("activation") == fused_activation
            ):
                layers = layers[:-1]
            elif (
                last["class_name"] == "Dense"
                and lc.get("activation") == fused_activation
            ):
                layers = layers[:-1] + [
                    {**last, "config": {**lc, "activation": "linear"}}
                ]
        self.layer_configs = tuple(
            {"class_name": l["class_name"], "config": dict(l.get("config", {}))}
            for l in layers
        )
        label_dtype = (
            np.int32 if loss_name == "softmax_ce" else np.float32
        )
        unknown = [m for m in metrics if m not in _METRICS]
        if unknown:
            raise ValueError(
                f"unsupported keras metrics {unknown}; known: "
                f"{sorted(_METRICS)}"
            )
        self._impl = JAXEstimator(
            model=KerasSequential(layer_configs=self.layer_configs),
            optimizer=parse_keras_optimizer(optimizer),
            loss=loss_name,
            metrics=[_METRICS[m] for m in metrics],
            num_epochs=num_epochs,
            batch_size=batch_size,
            feature_columns=feature_columns,
            label_column=label_column,
            label_dtype=label_dtype,
            shuffle=shuffle,
            callbacks=callbacks,
            seed=seed,
            **extra,
        )
        self.num_workers = num_workers

    # -- estimator surface (reference: tf/estimator.py fit/evaluate/...) --
    def fit(self, train_ds, evaluate_ds=None, num_epochs=None):
        return self._impl.fit(train_ds, evaluate_ds, num_epochs)

    def fit_on_df(self, train_df, evaluate_df=None, num_epochs=None):
        return self._impl.fit_on_df(
            train_df, evaluate_df, num_epochs,
            num_shards=max(1, self.num_workers),
        )

    # the reference's fit_on_spark name, for drop-in call sites
    fit_on_spark = fit_on_df

    def evaluate(self, ds, prefix: str = "eval_"):
        return self._impl.evaluate(ds, prefix=prefix)

    def get_model(self):
        return self._impl.get_model()

    def predict(self, x):
        return self._impl.predict(x)

    def save(self, checkpoint_dir, step=None):
        return self._impl.save(checkpoint_dir, step)

    def restore(self, checkpoint_dir, step=None, sample_x=None):
        return self._impl.restore(checkpoint_dir, step, sample_x=sample_x)

    def shutdown(self):
        self._impl.shutdown()

    @property
    def history(self):
        return self._impl.history
