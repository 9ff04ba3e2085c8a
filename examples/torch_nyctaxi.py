"""NYC-taxi fare regression on the Torch compat estimator.

Direct counterpart of the reference's examples/pytorch_nyctaxi.py:
the SAME torch model/optimizer/loss configuration surface, trained
data-parallel (gloo DDP over the SPMD gang) from a DataFrame.

Run: python examples/torch_nyctaxi.py [--smoke]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import raydp_tpu
import raydp_tpu.dataframe as rdf
from data_process import nyc_taxi_preprocess, synthetic_taxi


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--rows", type=int, default=100_000)
    args = parser.parse_args()
    n_rows = 4_000 if args.smoke else args.rows
    epochs = 2 if args.smoke else 8

    import torch

    from raydp_tpu.train.torch_estimator import TorchEstimator

    # The reference example's model shape (examples/pytorch_nyctaxi.py).
    class TaxiNet(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.fc1 = torch.nn.Linear(4, 256)
            self.fc2 = torch.nn.Linear(256, 128)
            self.fc3 = torch.nn.Linear(128, 1)

        def forward(self, x):
            x = torch.relu(self.fc1(x))
            x = torch.relu(self.fc2(x))
            return self.fc3(x)

    session = raydp_tpu.init(app_name="torch-nyctaxi", num_workers=2)
    try:
        df = nyc_taxi_preprocess(
            rdf.from_pandas(synthetic_taxi(n_rows), num_partitions=4)
        )
        model = TaxiNet()
        est = TorchEstimator(
            num_workers=2,
            model=model,
            optimizer=torch.optim.Adam(model.parameters(), lr=1e-3),
            loss=torch.nn.SmoothL1Loss(),
            feature_columns=[
                "hour", "day_of_week", "distance_km", "passenger_count"
            ],
            label_column="fare_amount",
            batch_size=256,
            num_epochs=epochs,
        )
        history = est.fit_on_df(df)
        est.shutdown()
        first, last = history[0], history[-1]
        print(f"train_loss {first['train_loss']:.4f} -> {last['train_loss']:.4f}")
        assert last["train_loss"] < first["train_loss"]
        print("torch_nyctaxi OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
