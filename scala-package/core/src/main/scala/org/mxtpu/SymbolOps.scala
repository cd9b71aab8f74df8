package org.mxtpu

/** GENERATED by tools/gen_scala_ops.py from the live op
  * registry (237 ops) — do not edit.  The role of the
  * reference scala-package macro expansion
  * (SymbolImplMacros.scala): one typed creator per
  * registered operator, delegating to Symbol.create.
  *
  * Usage: SymbolOps.FullyConnected("fc1")(
  *          "data" -> data)("num_hidden" -> 64)
  */
object SymbolOps {
  def Activation(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Activation", name)(inputs: _*)(attrs: _*)
  def BatchNorm(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("BatchNorm", name)(inputs: _*)(attrs: _*)
  def BilinearSampler(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("BilinearSampler", name)(inputs: _*)(attrs: _*)
  def BlockGrad(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("BlockGrad", name)(inputs: _*)(attrs: _*)
  def Cast(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Cast", name)(inputs: _*)(attrs: _*)
  def Concat(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Concat", name)(inputs: _*)(attrs: _*)
  def Convolution(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Convolution", name)(inputs: _*)(attrs: _*)
  def Correlation(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Correlation", name)(inputs: _*)(attrs: _*)
  def Crop(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Crop", name)(inputs: _*)(attrs: _*)
  def CuDNNBatchNorm(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("CuDNNBatchNorm", name)(inputs: _*)(attrs: _*)
  def Custom(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Custom", name)(inputs: _*)(attrs: _*)
  def Deconvolution(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Deconvolution", name)(inputs: _*)(attrs: _*)
  def Dropout(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Dropout", name)(inputs: _*)(attrs: _*)
  def ElementWiseSum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("ElementWiseSum", name)(inputs: _*)(attrs: _*)
  def Embedding(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Embedding", name)(inputs: _*)(attrs: _*)
  def FlashAttention(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("FlashAttention", name)(inputs: _*)(attrs: _*)
  def Flatten(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Flatten", name)(inputs: _*)(attrs: _*)
  def FullyConnected(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("FullyConnected", name)(inputs: _*)(attrs: _*)
  def GatedShortConv(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("GatedShortConv", name)(inputs: _*)(attrs: _*)
  def GridGenerator(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("GridGenerator", name)(inputs: _*)(attrs: _*)
  def IdentityAttachKLSparseReg(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("IdentityAttachKLSparseReg", name)(inputs: _*)(attrs: _*)
  def InstanceNorm(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("InstanceNorm", name)(inputs: _*)(attrs: _*)
  def KimiDeltaAttention(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("KimiDeltaAttention", name)(inputs: _*)(attrs: _*)
  def L2Normalization(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("L2Normalization", name)(inputs: _*)(attrs: _*)
  def LRN(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("LRN", name)(inputs: _*)(attrs: _*)
  def LeakyReLU(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("LeakyReLU", name)(inputs: _*)(attrs: _*)
  def LinearRegressionOutput(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("LinearRegressionOutput", name)(inputs: _*)(attrs: _*)
  def LogisticRegressionOutput(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("LogisticRegressionOutput", name)(inputs: _*)(attrs: _*)
  def MAERegressionOutput(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("MAERegressionOutput", name)(inputs: _*)(attrs: _*)
  def MakeLoss(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("MakeLoss", name)(inputs: _*)(attrs: _*)
  def Mamba2Mixer(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Mamba2Mixer", name)(inputs: _*)(attrs: _*)
  def MultiBoxDetection(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("MultiBoxDetection", name)(inputs: _*)(attrs: _*)
  def MultiBoxPrior(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("MultiBoxPrior", name)(inputs: _*)(attrs: _*)
  def MultiBoxTarget(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("MultiBoxTarget", name)(inputs: _*)(attrs: _*)
  def Pad(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Pad", name)(inputs: _*)(attrs: _*)
  def Pooling(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Pooling", name)(inputs: _*)(attrs: _*)
  def RMSNorm(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("RMSNorm", name)(inputs: _*)(attrs: _*)
  def RNN(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("RNN", name)(inputs: _*)(attrs: _*)
  def ROIPooling(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("ROIPooling", name)(inputs: _*)(attrs: _*)
  def Reshape(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Reshape", name)(inputs: _*)(attrs: _*)
  def RotaryEmbedding(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("RotaryEmbedding", name)(inputs: _*)(attrs: _*)
  def SVMOutput(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SVMOutput", name)(inputs: _*)(attrs: _*)
  def SequenceLast(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SequenceLast", name)(inputs: _*)(attrs: _*)
  def SequenceMask(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SequenceMask", name)(inputs: _*)(attrs: _*)
  def SequenceReverse(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SequenceReverse", name)(inputs: _*)(attrs: _*)
  def SliceChannel(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SliceChannel", name)(inputs: _*)(attrs: _*)
  def Softmax(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("Softmax", name)(inputs: _*)(attrs: _*)
  def SoftmaxActivation(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SoftmaxActivation", name)(inputs: _*)(attrs: _*)
  def SoftmaxOutput(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SoftmaxOutput", name)(inputs: _*)(attrs: _*)
  def SparseExperts(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SparseExperts", name)(inputs: _*)(attrs: _*)
  def SpatialTransformer(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SpatialTransformer", name)(inputs: _*)(attrs: _*)
  def SwapAxis(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SwapAxis", name)(inputs: _*)(attrs: _*)
  def SwiGLU(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("SwiGLU", name)(inputs: _*)(attrs: _*)
  def UpSampling(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("UpSampling", name)(inputs: _*)(attrs: _*)
  def WarpCTC(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("WarpCTC", name)(inputs: _*)(attrs: _*)
  def _CrossDeviceCopy(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_CrossDeviceCopy", name)(inputs: _*)(attrs: _*)
  def _arange(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_arange", name)(inputs: _*)(attrs: _*)
  def _copy(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_copy", name)(inputs: _*)(attrs: _*)
  def _crop_assign(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_crop_assign", name)(inputs: _*)(attrs: _*)
  def _crop_assign_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_crop_assign_scalar", name)(inputs: _*)(attrs: _*)
  def _div(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_div", name)(inputs: _*)(attrs: _*)
  def _div_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_div_scalar", name)(inputs: _*)(attrs: _*)
  def _equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_equal", name)(inputs: _*)(attrs: _*)
  def _equal_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_equal_scalar", name)(inputs: _*)(attrs: _*)
  def _full(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_full", name)(inputs: _*)(attrs: _*)
  def _grad_add(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_grad_add", name)(inputs: _*)(attrs: _*)
  def _greater(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_greater", name)(inputs: _*)(attrs: _*)
  def _greater_equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_greater_equal", name)(inputs: _*)(attrs: _*)
  def _greater_equal_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_greater_equal_scalar", name)(inputs: _*)(attrs: _*)
  def _greater_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_greater_scalar", name)(inputs: _*)(attrs: _*)
  def _hypot(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_hypot", name)(inputs: _*)(attrs: _*)
  def _hypot_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_hypot_scalar", name)(inputs: _*)(attrs: _*)
  def _identity_with_attr_like_rhs(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_identity_with_attr_like_rhs", name)(inputs: _*)(attrs: _*)
  def _lesser(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_lesser", name)(inputs: _*)(attrs: _*)
  def _lesser_equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_lesser_equal", name)(inputs: _*)(attrs: _*)
  def _lesser_equal_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_lesser_equal_scalar", name)(inputs: _*)(attrs: _*)
  def _lesser_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_lesser_scalar", name)(inputs: _*)(attrs: _*)
  def _maximum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_maximum", name)(inputs: _*)(attrs: _*)
  def _maximum_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_maximum_scalar", name)(inputs: _*)(attrs: _*)
  def _minimum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_minimum", name)(inputs: _*)(attrs: _*)
  def _minimum_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_minimum_scalar", name)(inputs: _*)(attrs: _*)
  def _minus(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_minus", name)(inputs: _*)(attrs: _*)
  def _minus_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_minus_scalar", name)(inputs: _*)(attrs: _*)
  def _mod(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_mod", name)(inputs: _*)(attrs: _*)
  def _mod_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_mod_scalar", name)(inputs: _*)(attrs: _*)
  def _mul(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_mul", name)(inputs: _*)(attrs: _*)
  def _mul_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_mul_scalar", name)(inputs: _*)(attrs: _*)
  def _not_equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_not_equal", name)(inputs: _*)(attrs: _*)
  def _not_equal_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_not_equal_scalar", name)(inputs: _*)(attrs: _*)
  def _ones(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_ones", name)(inputs: _*)(attrs: _*)
  def _plus(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_plus", name)(inputs: _*)(attrs: _*)
  def _plus_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_plus_scalar", name)(inputs: _*)(attrs: _*)
  def _power(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_power", name)(inputs: _*)(attrs: _*)
  def _power_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_power_scalar", name)(inputs: _*)(attrs: _*)
  def _random_normal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_random_normal", name)(inputs: _*)(attrs: _*)
  def _random_uniform(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_random_uniform", name)(inputs: _*)(attrs: _*)
  def _rdiv_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_rdiv_scalar", name)(inputs: _*)(attrs: _*)
  def _rminus_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_rminus_scalar", name)(inputs: _*)(attrs: _*)
  def _rmod_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_rmod_scalar", name)(inputs: _*)(attrs: _*)
  def _rpower_scalar(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_rpower_scalar", name)(inputs: _*)(attrs: _*)
  def _sample_normal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_sample_normal", name)(inputs: _*)(attrs: _*)
  def _sample_uniform(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_sample_uniform", name)(inputs: _*)(attrs: _*)
  def _slice_assign(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_slice_assign", name)(inputs: _*)(attrs: _*)
  def _sub(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_sub", name)(inputs: _*)(attrs: _*)
  def _sum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_sum", name)(inputs: _*)(attrs: _*)
  def _zeros(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("_zeros", name)(inputs: _*)(attrs: _*)
  def abs(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("abs", name)(inputs: _*)(attrs: _*)
  def adam_update(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("adam_update", name)(inputs: _*)(attrs: _*)
  def add_n(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("add_n", name)(inputs: _*)(attrs: _*)
  def arccos(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("arccos", name)(inputs: _*)(attrs: _*)
  def arccosh(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("arccosh", name)(inputs: _*)(attrs: _*)
  def arcsin(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("arcsin", name)(inputs: _*)(attrs: _*)
  def arcsinh(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("arcsinh", name)(inputs: _*)(attrs: _*)
  def arctan(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("arctan", name)(inputs: _*)(attrs: _*)
  def arctanh(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("arctanh", name)(inputs: _*)(attrs: _*)
  def argmax(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("argmax", name)(inputs: _*)(attrs: _*)
  def argmax_channel(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("argmax_channel", name)(inputs: _*)(attrs: _*)
  def argmin(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("argmin", name)(inputs: _*)(attrs: _*)
  def argsort(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("argsort", name)(inputs: _*)(attrs: _*)
  def batch_dot(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("batch_dot", name)(inputs: _*)(attrs: _*)
  def batch_take(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("batch_take", name)(inputs: _*)(attrs: _*)
  def broadcast_add(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_add", name)(inputs: _*)(attrs: _*)
  def broadcast_axes(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_axes", name)(inputs: _*)(attrs: _*)
  def broadcast_axis(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_axis", name)(inputs: _*)(attrs: _*)
  def broadcast_div(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_div", name)(inputs: _*)(attrs: _*)
  def broadcast_equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_equal", name)(inputs: _*)(attrs: _*)
  def broadcast_greater(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_greater", name)(inputs: _*)(attrs: _*)
  def broadcast_greater_equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_greater_equal", name)(inputs: _*)(attrs: _*)
  def broadcast_hypot(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_hypot", name)(inputs: _*)(attrs: _*)
  def broadcast_lesser(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_lesser", name)(inputs: _*)(attrs: _*)
  def broadcast_lesser_equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_lesser_equal", name)(inputs: _*)(attrs: _*)
  def broadcast_maximum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_maximum", name)(inputs: _*)(attrs: _*)
  def broadcast_minimum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_minimum", name)(inputs: _*)(attrs: _*)
  def broadcast_minus(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_minus", name)(inputs: _*)(attrs: _*)
  def broadcast_mod(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_mod", name)(inputs: _*)(attrs: _*)
  def broadcast_mul(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_mul", name)(inputs: _*)(attrs: _*)
  def broadcast_not_equal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_not_equal", name)(inputs: _*)(attrs: _*)
  def broadcast_plus(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_plus", name)(inputs: _*)(attrs: _*)
  def broadcast_power(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_power", name)(inputs: _*)(attrs: _*)
  def broadcast_sub(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_sub", name)(inputs: _*)(attrs: _*)
  def broadcast_to(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("broadcast_to", name)(inputs: _*)(attrs: _*)
  def cast(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("cast", name)(inputs: _*)(attrs: _*)
  def cbrt(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("cbrt", name)(inputs: _*)(attrs: _*)
  def ceil(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("ceil", name)(inputs: _*)(attrs: _*)
  def choose_element_0index(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("choose_element_0index", name)(inputs: _*)(attrs: _*)
  def clip(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("clip", name)(inputs: _*)(attrs: _*)
  def concat(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("concat", name)(inputs: _*)(attrs: _*)
  def cos(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("cos", name)(inputs: _*)(attrs: _*)
  def cosh(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("cosh", name)(inputs: _*)(attrs: _*)
  def crop(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("crop", name)(inputs: _*)(attrs: _*)
  def ctc_loss(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("ctc_loss", name)(inputs: _*)(attrs: _*)
  def degrees(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("degrees", name)(inputs: _*)(attrs: _*)
  def diag(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("diag", name)(inputs: _*)(attrs: _*)
  def dot(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("dot", name)(inputs: _*)(attrs: _*)
  def elemwise_add(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("elemwise_add", name)(inputs: _*)(attrs: _*)
  def elemwise_div(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("elemwise_div", name)(inputs: _*)(attrs: _*)
  def elemwise_mul(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("elemwise_mul", name)(inputs: _*)(attrs: _*)
  def elemwise_sub(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("elemwise_sub", name)(inputs: _*)(attrs: _*)
  def exp(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("exp", name)(inputs: _*)(attrs: _*)
  def expand_dims(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("expand_dims", name)(inputs: _*)(attrs: _*)
  def expm1(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("expm1", name)(inputs: _*)(attrs: _*)
  def fill_element_0index(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("fill_element_0index", name)(inputs: _*)(attrs: _*)
  def fix(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("fix", name)(inputs: _*)(attrs: _*)
  def flatten(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("flatten", name)(inputs: _*)(attrs: _*)
  def flip(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("flip", name)(inputs: _*)(attrs: _*)
  def floor(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("floor", name)(inputs: _*)(attrs: _*)
  def fused_bn_relu(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("fused_bn_relu", name)(inputs: _*)(attrs: _*)
  def fused_dot_epilogue(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("fused_dot_epilogue", name)(inputs: _*)(attrs: _*)
  def fused_scale_bias_dot(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("fused_scale_bias_dot", name)(inputs: _*)(attrs: _*)
  def gamma(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("gamma", name)(inputs: _*)(attrs: _*)
  def gammaln(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("gammaln", name)(inputs: _*)(attrs: _*)
  def identity(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("identity", name)(inputs: _*)(attrs: _*)
  def log(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("log", name)(inputs: _*)(attrs: _*)
  def log10(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("log10", name)(inputs: _*)(attrs: _*)
  def log1p(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("log1p", name)(inputs: _*)(attrs: _*)
  def log2(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("log2", name)(inputs: _*)(attrs: _*)
  def log_softmax(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("log_softmax", name)(inputs: _*)(attrs: _*)
  def logical_not(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("logical_not", name)(inputs: _*)(attrs: _*)
  def make_loss(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("make_loss", name)(inputs: _*)(attrs: _*)
  def max(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("max", name)(inputs: _*)(attrs: _*)
  def max_axis(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("max_axis", name)(inputs: _*)(attrs: _*)
  def mean(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("mean", name)(inputs: _*)(attrs: _*)
  def min(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("min", name)(inputs: _*)(attrs: _*)
  def min_axis(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("min_axis", name)(inputs: _*)(attrs: _*)
  def nanprod(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("nanprod", name)(inputs: _*)(attrs: _*)
  def nansum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("nansum", name)(inputs: _*)(attrs: _*)
  def negative(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("negative", name)(inputs: _*)(attrs: _*)
  def norm(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("norm", name)(inputs: _*)(attrs: _*)
  def normal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("normal", name)(inputs: _*)(attrs: _*)
  def one_hot(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("one_hot", name)(inputs: _*)(attrs: _*)
  def ones_like(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("ones_like", name)(inputs: _*)(attrs: _*)
  def pad(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("pad", name)(inputs: _*)(attrs: _*)
  def pick(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("pick", name)(inputs: _*)(attrs: _*)
  def prod(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("prod", name)(inputs: _*)(attrs: _*)
  def radians(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("radians", name)(inputs: _*)(attrs: _*)
  def rcbrt(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("rcbrt", name)(inputs: _*)(attrs: _*)
  def reciprocal(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("reciprocal", name)(inputs: _*)(attrs: _*)
  def relu(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("relu", name)(inputs: _*)(attrs: _*)
  def repeat(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("repeat", name)(inputs: _*)(attrs: _*)
  def reshape(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("reshape", name)(inputs: _*)(attrs: _*)
  def reverse(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("reverse", name)(inputs: _*)(attrs: _*)
  def rint(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("rint", name)(inputs: _*)(attrs: _*)
  def rmsprop_update(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("rmsprop_update", name)(inputs: _*)(attrs: _*)
  def rmspropalex_update(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("rmspropalex_update", name)(inputs: _*)(attrs: _*)
  def round(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("round", name)(inputs: _*)(attrs: _*)
  def rsqrt(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("rsqrt", name)(inputs: _*)(attrs: _*)
  def sgd_mom_update(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sgd_mom_update", name)(inputs: _*)(attrs: _*)
  def sgd_update(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sgd_update", name)(inputs: _*)(attrs: _*)
  def sigmoid(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sigmoid", name)(inputs: _*)(attrs: _*)
  def sign(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sign", name)(inputs: _*)(attrs: _*)
  def sin(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sin", name)(inputs: _*)(attrs: _*)
  def sinh(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sinh", name)(inputs: _*)(attrs: _*)
  def slice(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("slice", name)(inputs: _*)(attrs: _*)
  def slice_axis(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("slice_axis", name)(inputs: _*)(attrs: _*)
  def smooth_l1(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("smooth_l1", name)(inputs: _*)(attrs: _*)
  def softmax(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("softmax", name)(inputs: _*)(attrs: _*)
  def softmax_cross_entropy(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("softmax_cross_entropy", name)(inputs: _*)(attrs: _*)
  def softsign(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("softsign", name)(inputs: _*)(attrs: _*)
  def sort(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sort", name)(inputs: _*)(attrs: _*)
  def split(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("split", name)(inputs: _*)(attrs: _*)
  def sqrt(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sqrt", name)(inputs: _*)(attrs: _*)
  def square(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("square", name)(inputs: _*)(attrs: _*)
  def stack(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("stack", name)(inputs: _*)(attrs: _*)
  def stop_gradient(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("stop_gradient", name)(inputs: _*)(attrs: _*)
  def sum(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sum", name)(inputs: _*)(attrs: _*)
  def sum_axis(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("sum_axis", name)(inputs: _*)(attrs: _*)
  def swapaxes(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("swapaxes", name)(inputs: _*)(attrs: _*)
  def take(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("take", name)(inputs: _*)(attrs: _*)
  def tan(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("tan", name)(inputs: _*)(attrs: _*)
  def tanh(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("tanh", name)(inputs: _*)(attrs: _*)
  def tile(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("tile", name)(inputs: _*)(attrs: _*)
  def topk(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("topk", name)(inputs: _*)(attrs: _*)
  def transpose(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("transpose", name)(inputs: _*)(attrs: _*)
  def trunc(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("trunc", name)(inputs: _*)(attrs: _*)
  def uniform(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("uniform", name)(inputs: _*)(attrs: _*)
  def where(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("where", name)(inputs: _*)(attrs: _*)
  def zeros_like(name: String = "")(inputs: (String, Symbol)*)(attrs: (String, Any)*): Symbol =
    Symbol.create("zeros_like", name)(inputs: _*)(attrs: _*)
}
